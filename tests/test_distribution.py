"""Distribution layer: sharding rules, HLO collective parser, roofline
math.  (The full 512-device lower/compile proof lives in launch/dryrun.py;
these tests cover the logic units on the host mesh.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES
from repro.distribution import sharding as SH
from repro.distribution.hlo_analysis import (_shape_bytes,
                                             collective_bytes,
                                             parse_collectives)
from repro.distribution.roofline import RooflineTerms, model_flops
from repro.models import model as M


class FakeMesh:
    """Duck-typed mesh for rule unit tests (no devices needed)."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        n = 1
        for v in self.shape.values():
            n *= v
        return n


MESH1 = FakeMesh({"data": 16, "model": 16})
MESH2 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _leaf_specs(cfg, mesh):
    rules = SH.tp_rules(cfg, mesh)
    logical = M.param_logical(cfg)
    specs = M.param_specs(cfg)
    flat_l = jax.tree.leaves(
        logical, is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(s, (str, type(None))) for s in x))
    flat_s = jax.tree.leaves(specs)
    return [(l, s, SH._leaf_pspec(tuple(l), s.shape, rules, mesh))
            for l, s in zip(flat_l, flat_s)]


@pytest.mark.parametrize("arch", list(ARCHS))
def test_param_pspecs_divisible(arch):
    """Every sharded dim divides its mesh axis; axes unique per leaf."""
    cfg = ARCHS[arch]
    for mesh in (MESH1, MESH2):
        for logical, spec, pspec in _leaf_specs(cfg, mesh):
            assert len(pspec) <= len(spec.shape)
            used = [a for a in pspec if a is not None]
            assert len(used) == len(set(used)), (logical, pspec)
            for dim, axis in zip(spec.shape, tuple(pspec)):
                if axis is not None:
                    assert dim % mesh.shape[axis] == 0, \
                        (arch, logical, spec.shape, pspec)


def test_fsdp_only_for_big_archs():
    rules_small = SH.tp_rules(ARCHS["gemma3-4b"], MESH1)
    rules_big = SH.tp_rules(ARCHS["qwen2-72b"], MESH1)
    assert rules_small["embed"] is None
    assert rules_big["embed"] == "data"


def test_moe_expert_sharding_rule():
    """dbrx (16 experts) shards experts; granite-moe (40) falls back."""
    r_dbrx = SH.tp_rules(ARCHS["dbrx-132b"], MESH1)
    assert r_dbrx["experts"] == "model" and r_dbrx["mlp"] is None
    r_gm = SH.tp_rules(ARCHS["granite-moe-3b-a800m"], MESH1)
    assert r_gm["experts"] is None and r_gm["mlp"] == "model"


def test_input_shardings_match_specs():
    """Sharding tree structure matches input_specs for every cell kind."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch in ("qwen2-72b", "mamba2-130m", "internvl2-26b",
                 "musicgen-large"):
        cfg = ARCHS[arch]
        from repro.configs import shapes_for
        for sname in shapes_for(cfg):
            shape = SHAPES[sname]
            specs = M.input_specs(cfg, shape)
            shard = SH.input_shardings(cfg, mesh, shape)
            jax.tree.util = jax.tree_util
            s1 = jax.tree.structure(specs)
            s2 = jax.tree.structure(shard)
            assert s1 == s2, (arch, sname, s1, s2)


def test_dispatch_spec_reaches_the_production_mesh(monkeypatch):
    """``moe.DISPATCH_SPEC`` (the dry-run's ``moe-shard`` variants) changes
    the program lowered under the production mesh, whose axes are
    ``Auto``: on ``jax.make_mesh``'s default ``Explicit`` axes the
    constraint is refused, and the knob did nothing."""
    from repro.launch import mesh as MESH
    from repro.models import moe as MOE
    monkeypatch.setattr(MESH, "MESH_OVERRIDE", (1, 1))
    mesh = MESH.make_production_mesh()
    p = MOE.moe_init(jax.random.PRNGKey(0), 16, 8, 4, "swiglu")
    x = jnp.ones((2, 4, 16))

    def lowered(spec):
        monkeypatch.setattr(MOE, "DISPATCH_SPEC", spec)
        with mesh:
            return jax.jit(lambda p, x: MOE.moe_apply(
                p, x, top_k=2, capacity_factor=1.0,
                mlp_kind="swiglu")[0]).lower(p, x).as_text()

    assert lowered(("data", None)) != lowered(None)


# --- HLO parser ---------------------------------------------------------

HLO_SAMPLE = """
ENTRY %main.42 (p0: bf16[16,128]) -> bf16[16,2048] {
  %ag = bf16[16,2048]{1,0} all-gather(bf16[16,128]{1,0} %p0), dims={1}
  %ar = f32[256]{0} all-reduce(f32[256]{0} %x), to_apply=%add
  ROOT %t = bf16[16,2048]{1,0} add(%ag, %ag)
}
%body.7 (p: s32[]) -> s32[] {
  %rs = bf16[8,64]{1,0} reduce-scatter(bf16[8,1024]{1,0} %q), dims={1}
}
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[16,2048]") == 16 * 2048 * 2
    assert _shape_bytes("f32[256]") == 1024
    assert _shape_bytes("(f32[2,2], s8[4])") == 20


def test_parse_collectives_and_trip_scaling():
    per = parse_collectives(HLO_SAMPLE)
    assert per["main"]["all-gather"] == 16 * 2048 * 2
    assert per["main"]["all-reduce"] == 2 * 1024             # 2x conv.
    assert per["body.7"]["reduce-scatter"] == 8 * 1024 * 2   # operand
    tot1 = collective_bytes(HLO_SAMPLE, scan_trip_count=1)["total"]
    tot10 = collective_bytes(HLO_SAMPLE, scan_trip_count=10)["total"]
    assert tot10 - tot1 == 9 * 8 * 1024 * 2


# --- roofline math ------------------------------------------------------

def test_roofline_terms_and_bottleneck():
    t = RooflineTerms(arch="x", shape="train_4k", mesh="pod1", chips=256,
                      hlo_flops=1e18, hlo_bytes=1e15, coll_bytes=1e13,
                      model_flops=6e17)
    assert t.bottleneck == "compute"
    assert 0.5 < t.useful_ratio <= 0.61
    assert 0 < t.roofline_fraction <= 1.0


def test_model_flops_train_matches_6nd():
    cfg = ARCHS["granite-8b"]
    shape = SHAPES["train_4k"]
    f = model_flops(cfg, shape)
    base = 6 * cfg.param_count() * shape.global_batch * shape.seq_len
    assert f > base                      # attention term on top
    assert f < base * 1.5


def test_model_flops_decode_scales_with_batch():
    cfg = ARCHS["qwen2-72b"]
    d32 = model_flops(cfg, SHAPES["decode_32k"])
    assert d32 / SHAPES["decode_32k"].global_batch == pytest.approx(
        2 * cfg.param_count() + 4 * 32768 * cfg.n_layers * cfg.n_heads
        * cfg.d_head, rel=0.05)
