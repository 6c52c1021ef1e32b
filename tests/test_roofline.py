"""Roofline machinery tests: HLO collective parsing, analytic attention
model, and a miniature end-to-end dry-run on a subprocess-forced mesh."""

import os
import subprocess
import sys

import numpy as np

from repro.configs import INPUT_SHAPES, get_config
from repro.roofline.attention_model import attention_roofline
from repro.roofline.hlo import parse_collectives, shape_bytes


class TestShapeBytes:
    def test_simple(self):
        assert shape_bytes("f32[16,16]") == 1024
        assert shape_bytes("bf16[8]") == 16
        assert shape_bytes("pred[4,4]") == 16

    def test_tuple_result(self):
        assert shape_bytes("(f32[4,4], bf16[2,2])") == 64 + 8

    def test_scalar_and_unknown(self):
        assert shape_bytes("f32[]") == 4  # scalar: empty dims -> one element
        assert shape_bytes("token[]") == 0


class TestCollectiveParse:
    HLO = """
  %all-gather.1 = f32[16,4096]{1,0} all-gather(%p0), replica_groups=[16,16]<=[256], dimensions={1}
  %all-reduce.2 = bf16[1024]{0} all-reduce(%p1), replica_groups={{0,1,2,3}}, to_apply=%add
  %reduce-scatter.3 = f32[64]{0} reduce-scatter(%p2), replica_groups=[8,2]<=[16]
  %all-to-all.4 = bf16[32,32]{1,0} all-to-all(%p3), replica_groups=[4,4]<=[16]
  %collective-permute.5 = f32[10]{0} collective-permute(%p4), source_target_pairs={{0,1}}
"""

    def test_counts_and_kinds(self):
        summ = parse_collectives(self.HLO, default_group=16)
        kinds = summ.by_kind()
        assert set(kinds) == {
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute",
        }
        assert all(c == 1 for c, _ in kinds.values())

    def test_ring_traffic_model(self):
        summ = parse_collectives(self.HLO, default_group=16)
        ops = {o.kind: o for o in summ.ops}
        ag = ops["all-gather"]
        assert ag.group_size == 16
        assert ag.traffic_bytes == int(16 * 4096 * 4 * 15 / 16)
        ar = ops["all-reduce"]
        assert ar.group_size == 4
        assert ar.traffic_bytes == int(2 * 1024 * 2 * 3 / 4)
        rs = ops["reduce-scatter"]
        assert rs.group_size == 2
        assert rs.traffic_bytes == 64 * 4 * 1
        cp = ops["collective-permute"]
        assert cp.traffic_bytes == 40

    def test_while_detection(self):
        assert parse_collectives("%w = f32[2] while(%a), body=%b", default_group=4).has_while
        assert not parse_collectives(self.HLO, default_group=4).has_while

    def test_single_device_group_is_free(self):
        summ = parse_collectives(
            "%all-reduce.9 = f32[100]{0} all-reduce(%x), replica_groups={{0}}",
            default_group=1,
        )
        assert summ.total_traffic == 0


class TestAttentionModel:
    def test_causal_halves_flops(self):
        cfg = get_config("deepseek-7b")
        shape = INPUT_SHAPES["prefill_32k"]
        t = attention_roofline(cfg, shape)
        # fwd flops = n_layers * 4 B L (L/2) Hq hd
        expect = cfg.n_layers * 4 * shape.global_batch * 32768 * 16384 * cfg.n_heads * cfg.head_dim
        np.testing.assert_allclose(t.flops_global, expect, rtol=1e-6)

    def test_train_multiplier(self):
        cfg = get_config("deepseek-7b")
        tr = attention_roofline(cfg, INPUT_SHAPES["train_4k"], remat=True)
        cfg2 = get_config("deepseek-7b")
        fw = attention_roofline(cfg2, INPUT_SHAPES["train_4k"], remat=False)
        np.testing.assert_allclose(tr.flops_global / fw.flops_global, 4.0 / 3.0, rtol=1e-6)

    def test_decode_has_no_correction(self):
        cfg = get_config("deepseek-7b")
        t = attention_roofline(cfg, INPUT_SHAPES["decode_32k"])
        assert t.flops_global == 0.0

    def test_ssm_has_no_attention(self):
        cfg = get_config("mamba2-130m")
        t = attention_roofline(cfg, INPUT_SHAPES["train_4k"])
        assert t.flops_global == 0.0

    def test_window_caps_context(self):
        cfg = get_config("qwen2-1.5b")
        full = attention_roofline(cfg, INPUT_SHAPES["prefill_32k"])
        import dataclasses

        win = attention_roofline(
            cfg, dataclasses.replace(INPUT_SHAPES["long_500k"], kind="prefill")
        )
        # long_500k uses the sliding window: per-token kv length 4096 vs 16384
        per_tok_full = full.flops_global / (32 * 32768)
        per_tok_win = win.flops_global / (1 * 524288)
        assert per_tok_win < per_tok_full


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import repro.launch.dryrun as dr
import jax
from jax.sharding import AxisType
from repro.core.layouts import AXIS_DATA, AXIS_MODEL
mesh = jax.make_mesh((2, 4), (AXIS_DATA, AXIS_MODEL), axis_types=(AxisType.Auto,) * 2)
import repro.configs.base as base
import dataclasses
# shrink shapes so the mini run is quick
base.INPUT_SHAPES = {
    "train_4k": dataclasses.replace(base.INPUT_SHAPES["train_4k"], seq_len=128, global_batch=4),
    "decode_32k": dataclasses.replace(base.INPUT_SHAPES["decode_32k"], seq_len=256, global_batch=4),
}
dr.INPUT_SHAPES = base.INPUT_SHAPES
orig_get = dr.get_config
dr.get_config = lambda a, **kw: orig_get(a, smoke=True)
for shape in ("train_4k", "decode_32k"):
    res = dr.lower_combo("qwen2-1.5b", shape, mesh, verbose=False)
    assert res.ok and not res.skipped, res
    r = res.report
    assert r["flops_per_device"] > 0
    assert r["dominant"] in ("compute", "memory", "collective")
print("MINI_DRYRUN_OK")
"""


def test_mini_dryrun_end_to_end(tmp_path):
    """The full dry-run pipeline (lower, compile, fit, roofline) on a tiny
    mesh/config in a subprocess."""
    script = tmp_path / "mini_dryrun.py"
    script.write_text(MINI_DRYRUN)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, str(script)], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MINI_DRYRUN_OK" in proc.stdout
