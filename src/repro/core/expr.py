"""Deferred-expression DAG — the client side of the lazy offload planner.

DESIGN.md §6: the follow-up paper (arXiv:1805.11800) shows Alchemist's win
evaporating when an application naively collects every result back to Spark
between offloaded calls. The cure is structural: client-side operations build
a small DAG of deferred ops instead of executing eagerly, and the planner
(:mod:`repro.core.planner`) lowers the DAG onto the async task queue only
when a result is explicitly demanded. A value produced by one routine and
consumed by the next never crosses the bridge at all — it stays resident on
the session, exactly like the real Alchemist server's matrices that
"physically live on the MPI side".

Three node kinds:

- :class:`SendExpr`    — a host array that will become engine-resident; carries
  a content key so identical payloads dedup into one resident matrix.
- :class:`RunExpr`     — a deferred ``(library, routine)`` invocation whose args
  may be other nodes, :class:`~repro.core.handles.AlMatrix` handles, or
  scalars.
- :class:`ProjExpr`    — index ``i`` of a multi-output :class:`RunExpr`
  (``truncated_svd`` returns ``(U, s, V)``; each output is its own node).

:class:`LazyMatrix` is the user-facing wrapper: it holds a node plus the
planner that will execute it, supports ``@`` for deferred matmul, and
``collect()`` for the one explicit bridge crossing.

Every ElementalLib routine has a shape rule in :data:`SHAPE_RULES`, so
deferred chains validate at graph-build time (a mismatched ``gemm`` raises
:class:`~repro.core.errors.ShapeError` where it is written, not deep inside
the task queue) and the memory governor can reserve output bytes before a
routine runs (DESIGN.md §7).
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
from typing import TYPE_CHECKING, Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.errors import ShapeError

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids an import cycle
    from repro.core.planner import OffloadPlanner

_EXPR_IDS = itertools.count(1)


# ---------------------------------------------------------------------------
# Per-routine shape rules
# ---------------------------------------------------------------------------
#
# Each rule maps (arg shapes, params) -> one shape per routine output, letting
# LazyMatrix chains validate at graph-build time: a dimension mismatch raises
# a client-side ShapeError where the call is written, instead of surfacing as
# a deep task-queue failure after the DAG started executing. An entry of None
# in ``shapes`` means "unknown" (scalar arg, or an upstream node without a
# rule) — rules stay silent rather than guessing. Matrix outputs are 2-tuples;
# vectors are 1-tuples; scalars are ``()``.

ShapeLike = Optional[Tuple[int, ...]]
ShapeRule = Callable[[Sequence[ShapeLike], Dict[str, Any]], Tuple[ShapeLike, ...]]


def _require_2d(routine: str, pos: int, s: Tuple[int, ...]) -> None:
    if len(s) != 2:
        raise ShapeError(f"{routine}: operand {pos} must be 2D, got shape {s}")


def _rule_gemm(shapes: Sequence[ShapeLike], params: Dict[str, Any]):
    if len(shapes) < 2:
        raise ShapeError(f"gemm expects 2 matrix operands, got {len(shapes)}")
    a, b = shapes[0], shapes[1]
    if a is None or b is None:
        return (None,)
    _require_2d("gemm", 0, a)
    _require_2d("gemm", 1, b)
    if a[1] != b[0]:
        raise ShapeError(
            f"gemm: inner dimensions do not agree: {a[0]}x{a[1]} @ {b[0]}x{b[1]}"
        )
    return ((a[0], b[1]),)


def _svd_k(shapes: Sequence[ShapeLike], params: Dict[str, Any], routine: str):
    a = shapes[0] if shapes else None
    if a is None:
        return None, None
    _require_2d(routine, 0, a)
    if "k" not in params:
        # Not passed as a keyword (library default, or smuggled positionally
        # — which the keyword-only adapters reject at execution anyway):
        # don't validate or infer from an invented value.
        return a, None
    k = int(params["k"])
    if k < 1 or k > min(a):
        raise ShapeError(
            f"{routine}: k={k} out of range for a {a[0]}x{a[1]} matrix "
            f"(need 1 <= k <= {min(a)})"
        )
    return a, k


def _rule_truncated_svd(shapes, params, routine="truncated_svd"):
    a, k = _svd_k(shapes, params, routine)
    if a is None or k is None:
        return (None, None, None)
    return ((a[0], k), (k,), (a[1], k))  # U, s, V


def _rule_pca(shapes, params):
    a, k = _svd_k(shapes, params, "pca")
    if a is None or k is None:
        return (None, None, None)
    return ((a[1], k), (a[0], k), (k,))  # components, scores, explained_var


def _rule_tsqr(shapes, params):
    a = shapes[0] if shapes else None
    if a is None:
        return (None, None)
    _require_2d("tsqr", 0, a)
    if a[0] < a[1]:
        raise ShapeError(
            f"tsqr expects a tall-skinny matrix (rows >= cols), got {a[0]}x{a[1]}"
        )
    return ((a[0], a[1]), (a[1], a[1]))  # Q, R


def _rule_ridge(shapes, params):
    if len(shapes) < 2:
        raise ShapeError(f"ridge expects (A, b), got {len(shapes)} operands")
    a, b = shapes[0], shapes[1]
    if a is None or b is None:
        return (None,)
    _require_2d("ridge", 0, a)
    _require_2d("ridge", 1, b)
    if b != (a[0], 1):
        raise ShapeError(
            f"ridge: b must be {a[0]}x1 to match a {a[0]}x{a[1]} A, got {b[0]}x{b[1]}"
        )
    return ((a[1], 1),)


def _rule_scalar(routine: str) -> ShapeRule:
    def rule(shapes, params):
        a = shapes[0] if shapes else None
        if a is not None:
            _require_2d(routine, 0, a)
        return ((),)

    return rule


#: routine name -> shape rule, spanning every ElementalLib routine.
#: Third-party libraries extend this table at registration:
#: ``Library.register(..., shape_rule=...)`` routes through
#: :func:`register_shape_rule`, so their routines get the same graph-build
#: validation and governor output pricing as the built-ins (DESIGN.md §7).
SHAPE_RULES: Dict[str, ShapeRule] = {
    "gemm": _rule_gemm,
    "multiply": _rule_gemm,
    "truncated_svd": lambda s, p: _rule_truncated_svd(s, p, "truncated_svd"),
    "randomized_svd": lambda s, p: _rule_truncated_svd(s, p, "randomized_svd"),
    "pca": _rule_pca,
    "tsqr": _rule_tsqr,
    "ridge": _rule_ridge,
    "condest": _rule_scalar("condest"),
    "normest": _rule_scalar("normest"),
    "sigma_max": _rule_scalar("sigma_max"),
}


def register_shape_rule(
    routine: str, rule: ShapeRule, *, override: bool = False
) -> None:
    """Register a shape rule for a (third-party) routine name.

    The table is engine-global and keyed by routine name — the same key
    ``ac.run``/``OffloadPlanner.run`` dispatch on — so a registered rule
    immediately gives the routine graph-build ShapeError validation and
    output-byte pricing for governor admission (DESIGN.md §7). Registering a
    *different* rule under an existing name raises unless ``override=True``:
    two libraries silently disagreeing about one routine name is a bug, not
    a merge.
    """
    if not callable(rule):
        raise TypeError(f"shape rule for {routine!r} must be callable, got {rule!r}")
    existing = SHAPE_RULES.get(routine)
    if existing is not None and not _same_rule(existing, rule) and not override:
        raise ShapeError(
            f"routine {routine!r} already has a shape rule; pass override=True "
            "to replace it"
        )
    SHAPE_RULES[routine] = rule


def _same_rule(a: ShapeRule, b: ShapeRule) -> bool:
    """Are two rule callables the same rule? Identity, or the same code
    object — a library class defining its rule inline (lambda/nested def in
    ``__init__``) creates a fresh function per instantiation, and registering
    that library in a second session must not read as a conflict."""
    if a is b:
        return True
    code_a = getattr(a, "__code__", None)
    return code_a is not None and code_a is getattr(b, "__code__", None)


def arg_shape(a: Any) -> ShapeLike:
    """Best-known shape of a routine argument: Expr nodes and AlMatrix
    handles carry one; scalars and unknown upstream outputs are None."""
    s = getattr(a, "shape", None)
    if s is None:
        return None
    try:
        return tuple(int(d) for d in s)
    except (TypeError, ValueError):
        return None


def infer_run_shapes(
    routine: str,
    shapes: Sequence[ShapeLike],
    params: Dict[str, Any],
    n_outputs: Optional[int] = None,
) -> Optional[Tuple[ShapeLike, ...]]:
    """Apply the routine's shape rule; returns one shape per output, or None
    when no rule exists. Raises :class:`ShapeError` on operand mismatches and
    on an ``n_outputs`` that disagrees with the rule (only checked for
    multi-output requests: ``n_outputs=1`` legitimately means "hand me the
    whole result", whatever its arity)."""
    rule = SHAPE_RULES.get(routine)
    if rule is None:
        return None
    out = rule(list(shapes), dict(params))
    if n_outputs is not None and n_outputs > 1 and n_outputs != len(out):
        raise ShapeError(
            f"{routine} produces {len(out)} outputs, but n_outputs={n_outputs}"
        )
    return out


def peeked_state(val: Any) -> str:
    """Classify a planner-peeked value (``OffloadPlanner.peek``) into the
    uniform placement-state vocabulary the v2 handles expose (DESIGN.md §9):
    ``deferred`` (never lowered), ``pending`` (queued/in flight), or the
    underlying :class:`~repro.core.handles.AlMatrix` lifecycle state
    (``materialized``/``spilled``/``failed``/``freed``). Driver-side values
    (scalars, vectors, already-collected arrays) read as ``materialized``.
    Never forces execution. Shared by :class:`~repro.core.client.AlArray`
    and sparklike's ``LazyRowMatrix``."""
    from repro.core.futures import AlFuture
    from repro.core.handles import AlMatrix

    if val is None:
        return "deferred"
    if isinstance(val, AlFuture):
        if not val.done():
            return "pending"
        if val.exception() is not None:
            return "failed"
        val = val.result()
    return val.state if isinstance(val, AlMatrix) else "materialized"


def raw_bytes(arr: np.ndarray) -> np.ndarray:
    """The array's bytes in C (row) order as a flat uint8 array — a view of
    a C-contiguous input, one copy of any other. A ``memoryview`` cast would
    refuse dtypes without a buffer format (bfloat16), and ``tobytes()``
    always copies."""
    return np.ascontiguousarray(arr).reshape(-1).view(np.uint8)


def content_key(array: Any) -> Tuple:
    """Content-identity of a host array: (shape, dtype, sha1 of the bytes).

    This keys the planner's per-session resident-matrix cache: two sends of
    equal payloads resolve to one engine-resident matrix, regardless of
    whether the caller reused the ndarray object or rebuilt it. SHA-1 reads
    a C-contiguous array in place; row order defines the key, so any other
    layout is made contiguous first.
    """
    key_fn = getattr(array, "content_key", None)
    if callable(key_fn):
        # Shard-staged wire payloads (transport.StagedShards) hash their
        # logical slabs in place — same (shape, dtype, sha1) triple, no
        # reassembly copy.
        return key_fn()
    arr = np.asarray(array)
    with TraceAnnotation("al.store.key", nbytes=arr.nbytes, side="host"):
        digest = hashlib.sha1(raw_bytes(arr)).hexdigest()
    return (tuple(int(d) for d in arr.shape), str(arr.dtype), digest)


@dataclasses.dataclass(frozen=True, eq=False)
class Expr:
    """A node in the deferred-op DAG. Identity (not structure) keyed: the
    same node object consumed twice is one computation with two consumers."""

    id: int = dataclasses.field(default_factory=lambda: next(_EXPR_IDS), init=False)

    @property
    def shape(self) -> Optional[Tuple[int, int]]:
        return None

    @property
    def dtype(self):
        return None


@dataclasses.dataclass(frozen=True, eq=False)
class SendExpr(Expr):
    """A host→engine transfer, deferred. ``key`` is :func:`content_key` of
    the payload (computed once, at graph-build time)."""

    array: Any = None
    name: str = ""
    key: Tuple = ()
    _shape: Tuple[int, int] = ()
    _dtype: str = ""

    @staticmethod
    def of(array: Any, name: str = "", *, snapshot: bool = True) -> "SendExpr":
        # Snapshot mutable host arrays: the content key is computed now, and
        # a caller mutating the ndarray between graph build and lowering must
        # not ship different bytes under the old key (which would poison the
        # resident-matrix cache). jax.Arrays are immutable — no copy needed —
        # and internal callers that just materialized a private array
        # (e.g. sparklike offload's to_numpy()) pass snapshot=False to skip
        # the redundant O(m·n) copy.
        if isinstance(array, np.ndarray):
            if snapshot:
                with TraceAnnotation("al.host.copy", nbytes=array.nbytes, site="snapshot"):
                    array = np.array(array)  # fresh copy
        elif not hasattr(array, "shape"):
            array = np.array(array)  # lists etc.: conversion already copies
        arr = array
        if len(arr.shape) != 2:
            raise ValueError(
                f"SendExpr expects a 2D matrix, got shape {tuple(arr.shape)}"
            )
        return SendExpr(
            array=array,
            name=name,
            key=content_key(array),
            _shape=tuple(int(d) for d in arr.shape),
            _dtype=str(arr.dtype),
        )

    @property
    def shape(self) -> Tuple[int, int]:
        return self._shape

    @property
    def dtype(self) -> str:
        return self._dtype

    def __repr__(self) -> str:
        return f"SendExpr(id={self.id}, shape={self._shape}, name={self.name!r})"


@dataclasses.dataclass(frozen=True, eq=False)
class RunExpr(Expr):
    """A deferred routine invocation. ``args`` entries are Expr nodes,
    AlMatrix handles (already resident), or plain scalars; ``params`` are
    codec-packable scalars only."""

    library: str = ""
    routine: str = ""
    args: Tuple[Any, ...] = ()
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    n_outputs: int = 1

    def output_shapes(self) -> Optional[Tuple[ShapeLike, ...]]:
        """One inferred shape per routine output via :data:`SHAPE_RULES`,
        or None when the routine has no rule. May raise ShapeError."""
        return infer_run_shapes(
            self.routine,
            [arg_shape(a) for a in self.args],
            self.params,
            self.n_outputs,
        )

    @property
    def shape(self) -> Optional[Tuple[int, int]]:
        try:
            shapes = self.output_shapes()
        except ShapeError:
            # Construction already validated; a late error (e.g. an upstream
            # shape learned afterwards) surfaces on execution, not here.
            return None
        if shapes and len(shapes) == 1 and shapes[0] is not None and len(shapes[0]) == 2:
            return shapes[0]
        return None

    def __repr__(self) -> str:
        return (
            f"RunExpr(id={self.id}, {self.library}.{self.routine}, "
            f"args={len(self.args)}, n_outputs={self.n_outputs})"
        )


@dataclasses.dataclass(frozen=True, eq=False)
class ProjExpr(Expr):
    """Output ``index`` of a multi-output :class:`RunExpr`."""

    parent: RunExpr = None
    index: int = 0

    @property
    def shape(self) -> Optional[Tuple[int, int]]:
        try:
            shapes = self.parent.output_shapes()
        except ShapeError:
            return None
        if shapes is None or self.index >= len(shapes):
            return None
        s = shapes[self.index]
        return s if s is not None and len(s) == 2 else None

    def __repr__(self) -> str:
        return f"ProjExpr(id={self.id}, parent={self.parent.id}, index={self.index})"


def iter_nodes(root: Expr):
    """Yield the DAG under ``root`` in dependency order (producers first)."""
    seen = set()

    def walk(node: Expr):
        if node.id in seen:
            return
        seen.add(node.id)
        if isinstance(node, ProjExpr):
            yield from walk(node.parent)
        elif isinstance(node, RunExpr):
            for a in node.args:
                if isinstance(a, Expr):
                    yield from walk(a)
        yield node

    yield from walk(root)


class LazyMatrix:
    """Client-side proxy for a deferred engine-resident matrix.

    Mirrors the paper's AlMatrix contract one level earlier: where an
    AlMatrix is a handle to data already on the engine, a LazyMatrix is a
    handle to data the planner has not even moved yet. Operations chain
    without executing; only :meth:`collect` crosses the bridge.
    """

    # Binary ops with ndarrays must reach our reflected operators: without
    # this, `ndarray @ LazyMatrix` coerces the proxy into a 0-d object array
    # and raises inside numpy before __rmatmul__ is ever consulted.
    __array_ufunc__ = None
    __array_priority__ = 1000

    def __init__(self, expr: Expr, planner: "OffloadPlanner"):
        self.expr = expr
        self.planner = planner

    @property
    def shape(self) -> Optional[Tuple[int, int]]:
        return self.expr.shape

    @property
    def dtype(self):
        return self.expr.dtype

    # -- chaining -----------------------------------------------------------
    def __matmul__(self, other: Any) -> "LazyMatrix":
        lib, routine = self.planner.matmul_routine
        return self.planner.run(lib, routine, self, other)

    def __rmatmul__(self, other: Any) -> "LazyMatrix":
        lib, routine = self.planner.matmul_routine
        return self.planner.run(lib, routine, other, self)

    # -- execution ----------------------------------------------------------
    def materialize(self):
        """Force execution; returns the engine-side value (an AlMatrix
        handle, or a driver-side scalar/vector) without crossing the bridge
        for matrix data."""
        return self.planner.materialize(self)

    def collect(self):
        """Execute the DAG under this node and bring the result client-side
        — the single explicit bridge crossing."""
        return self.planner.collect(self)

    def __repr__(self) -> str:
        return f"LazyMatrix({self.expr!r})"
