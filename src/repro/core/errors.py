"""Structured error hierarchy for the Alchemist engine."""

from __future__ import annotations


class AlchemistError(Exception):
    """Base class for all engine errors."""


class SessionError(AlchemistError):
    """Session lifecycle problems (stopped context, double-stop, ...)."""


class WorkerAllocationError(AlchemistError):
    """Not enough free workers to satisfy an allocation request.

    Mirrors the paper's "assuming a sufficient number of workers is
    available" failure mode (§2.4, §3.2 step 3).
    """


class AdmissionTimeout(WorkerAllocationError):
    """A queued ``connect()`` waited out its admission timeout (DESIGN.md §9).

    Subclasses :class:`WorkerAllocationError`: callers that handled the old
    fail-fast allocation error keep working when queued admission is enabled.
    Raised *before* any worker group, session, or governor registration
    exists, so there is nothing to clean up.
    """


class LibraryError(AlchemistError):
    """Unknown library / routine, or a routine signature mismatch."""


class HandleError(AlchemistError):
    """Invalid or foreign AlMatrix handle (wrong session, freed, ...)."""


class LayoutError(AlchemistError):
    """Illegal layout conversion or a layout/mesh mismatch."""


class ShapeError(AlchemistError):
    """A deferred-op DAG failed shape inference at graph-build time: routine
    operands whose dimensions cannot compose (caught client-side, where the
    paper's driver would reject the call, instead of deep in the task queue)."""


class ParameterError(AlchemistError):
    """Bad scalar-parameter pack/unpack (Parameters header analogue)."""


class TaskError(AlchemistError):
    """Asynchronous task-queue failures: a future that timed out, a queue
    used after close, or a pending handle whose producing task failed
    (the original exception is chained as ``__cause__``)."""


class QueueClosedError(TaskError, SessionError):
    """Work submitted to a task queue after it closed. A session's queue
    closes with the session, so this is also a session-lifecycle error: a
    client whose session a server stop closes mid-request sees a
    ``SessionError`` whichever check the stop wins against."""
