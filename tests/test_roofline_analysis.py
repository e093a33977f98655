"""HLO roofline analyzer + sharding rules: unit coverage.

The analyzer feeds §Roofline, so its parsing must be exact on known HLO;
sharding rules are checked against an abstract production mesh (no devices
needed to validate PartitionSpecs).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch.hlo_analysis import (
    HBM_BW,
    PEAK_FLOPS,
    analyze_hlo,
    parse_hlo,
    roofline_terms,
)

SAMPLE = """
HloModule jit_f

%body (arg: (s32[], f32[8,8], f32[8,8])) -> (s32[], f32[8,8], f32[8,8]) {
  %arg = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  %c1 = s32[] constant(1)
  %i = s32[] get-tuple-element(%arg), index=0
  %a = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %w = f32[8,8]{1,0} get-tuple-element(%arg), index=2
  %ni = s32[] add(%i, %c1)
  %d = f32[8,8]{1,0} dot(%a, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}, to_apply=%body
  ROOT %t = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%ni, %ar, %w)
}

%cond (arg2: (s32[], f32[8,8], f32[8,8])) -> pred[] {
  %arg2 = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  %c7 = s32[] constant(7)
  %i2 = s32[] get-tuple-element(%arg2), index=0
  ROOT %lt = pred[] compare(%i2, %c7), direction=LT
}

ENTRY %main (x: f32[8,8], w0: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %w0 = f32[8,8]{1,0} parameter(1)
  %c0 = s32[] constant(0)
  %tup = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%c0, %x, %w0)
  %wh = (s32[], f32[8,8]{1,0}, f32[8,8]{1,0}) while(%tup), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%wh), index=1
}
"""


def test_while_trip_scaling():
    stats = analyze_hlo(SAMPLE)
    # dot: 2*8*8*8 flops, scaled by trip count 7
    assert stats.flops == 7 * 2 * 8 * 8 * 8
    # all-reduce operand: 8*8*4 bytes, scaled by 7
    assert stats.collective_bytes == 7 * 8 * 8 * 4
    assert stats.count_by_kind["all-reduce"] == 7


def test_parse_handles_tuple_types():
    comps, entry = parse_hlo(SAMPLE)
    assert entry == "%main"
    ops = {i.op for i in comps["%body"].instrs}
    assert {"dot", "all-reduce", "add", "tuple"} <= ops


def test_roofline_terms_math():
    r = roofline_terms(
        hlo_flops=PEAK_FLOPS,  # exactly 1 s of compute
        hlo_bytes=HBM_BW / 2,  # 0.5 s of memory
        collective_bytes=0.0,
        chips=4,
        model_flops=2 * PEAK_FLOPS,  # 0.5 s useful per chip
    )
    assert r["dominant"] == "compute"
    assert r["bound_s"] == pytest.approx(1.0)
    assert r["roofline_fraction"] == pytest.approx(0.5)


def test_real_compiled_module_roundtrip():
    """Analyzer numbers on a real compiled scan match hand math."""
    L, D = 5, 32

    def f(x, w):
        def body(c, _):
            return c @ w, None

        y, _ = jax.lax.scan(body, x, None, length=L)
        return y

    x = jax.ShapeDtypeStruct((D, D), jnp.float32)
    w = jax.ShapeDtypeStruct((D, D), jnp.float32)
    text = jax.jit(f).lower(x, w).compile().as_text()
    stats = analyze_hlo(text)
    assert stats.flops == L * 2 * D * D * D


# ----------------------------------------------------------------------
# sharding rules on the (abstract) production mesh
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def rules():
    from repro.configs import get_config
    from repro.distributed.sharding import ShardingRules

    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((16, 16), ("data", "model"))
    return ShardingRules(get_config("stablelm_12b"), mesh)


def test_param_specs(rules):
    assert rules.param_spec(("embed", "table"), (100352, 5120)) == P("model", None)
    # q heads 32 % 16 == 0 -> sharded; kv heads 8 % 16 != 0 -> replicated
    assert rules.param_spec(("attn", "wq"), (5120, 32, 160)) == P(None, "model", None)
    assert rules.param_spec(("attn", "wk"), (5120, 8, 160)) == P(None, None, None)
    assert rules.param_spec(("mlp", "w_gate"), (5120, 13824)) == P(None, "model")
    # stage-stacked leaf: leading repeat dim stays unsharded
    assert rules.param_spec(("attn", "wq"), (40, 5120, 32, 160)) == P(
        None, None, "model", None
    )
    assert rules.param_spec(("norm1", "scale"), (5120,)) == P(None)


def test_zero1_extends_first_free_dim(rules):
    base = rules.param_spec(("attn", "wq"), (5120, 32, 160))
    z = rules.zero1_spec(base, (5120, 32, 160))
    assert z == P(("data",), "model", None)


def test_cache_specs(rules):
    # kv heads 8 not divisible by 16 -> sequence goes to model
    assert rules.cache_spec(("k",), (128, 8, 32768, 160)) == P(
        ("data",), None, "model", None
    )
    # divisible kv heads -> heads to model
    assert rules.cache_spec(("k",), (128, 16, 32768, 160)) == P(
        ("data",), "model", None, None
    )
    # batch 1 (long_500k): no data sharding
    assert rules.cache_spec(("k",), (1, 8, 524288, 160)) == P(
        None, None, "model", None
    )


def test_batch_specs(rules):
    assert rules.batch_spec("tokens", (256, 4096)) == P(("data",), None)
    assert rules.batch_spec("pos", ()) == P()
    assert rules.batch_spec("tokens", (1, 4096)) == P(None, None)
