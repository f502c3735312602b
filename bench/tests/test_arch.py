"""An architecture is a module of its own, ``bench/arch/<arch>.py``, named
by the configuration file's ``"arch"``.

Moving the decoder into ``bench/arch/decoder.py`` changed nothing that is
measured: the weights, the reference's logits and every count are the
numbers that the harness gave before it had architecture modules
(hard-coded below from a run of that code on the CPU).  A second
architecture needs only a new file, and its reference, not a copy of the
decoder's, decides ``correct``."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import counts
import registry
import tiny
from weights import make_params

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _tree_digest(params) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves,
                             key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


PARAMS = {
    "tiny": "9b7eea574c9fec60565d186a2e0080fc"
            "4599fa4efba8f8f5abdfd3de43b80a2f",
    "tiny-moe": "473d55ecdad61fa602266e1fc986a89d"
                "7d43d3ce9aa703ea21fcc42f61a36f7e",
}

LOGITS = {
    ("tiny", None): "3af8d969b161509d33f237464e7cd590"
                    "3df16b4542ba2261de5d0238c31fe9cb",
    ("tiny", "fp8"): "b4efd46a0f2a7621e2cd657232396997"
                     "d85aad456f44a6e03dfb473a421c2dca",
    ("tiny-moe", None): "859749011c5002101aa3fce883cf560b"
                        "a59adba832d35ba4611a0676eef1c9af",
    ("tiny-moe", "fp8"): "ca2f8aefef4dafce8c41da377d42d0a5"
                         "f074e4aa77a2c8f95329bedbd779adab",
}

_TINY = {"tiny": tiny.TINY, "tiny-moe": tiny.TINY_MOE}


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_weights_are_bit_identical(name):
    assert _tree_digest(make_params(_TINY[name], 7)) == PARAMS[name]


@pytest.mark.parametrize("name,quant", sorted(LOGITS, key=str))
def test_decoder_reference_logits_are_bit_identical(name, quant):
    c = _TINY[name]
    toks = np.random.default_rng(3).integers(0, c["vocab_size"],
                                             40).astype(np.int32)
    lg = registry.arch(c).logits(make_params(c, 7), c, toks, 64,
                                 quant=quant)
    digest = hashlib.sha256(np.asarray(lg, np.float32).tobytes())
    assert digest.hexdigest() == LOGITS[(name, quant)]


# (batch 1..8), (contexts 1, 129, 1020, 2048), three batches of
# contexts, (context 1, 1021, 2048) x (logits off, on), prompts 128,
# 1024, 1536
COUNTS = {
    "granite-8b": dict(
        decode_weight_bytes=[8254701568, 8254709760, 8254717952,
                             8254726144, 8254734336, 8254742528,
                             8254750720, 8254758912],
        state_bytes=[73728, 9510912, 75202560, 150994944],
        decode_step_bytes=[8269447168, 8618942464, 9065177088],
        token_flops=[7852032000.0, 8254685184.0, 8152842240.0,
                     8555495424.0, 8455716864.0, 8858370048.0],
        prefill_flops=[1007859793920.0, 8195351248896.0,
                       12408789663744.0],
        param_bytes=8657346560,
        gemv_shapes=[(4096, 4096), (1024, 4096), (14336, 4096),
                     (4096, 14336), (49152, 4096)]),
    "granite-moe-3b-a800m": dict(
        decode_weight_bytes=[1766529023.9999995, 2732899737.5999994,
                             3505996922.879999, 4124475285.503999,
                             4619258590.0032, 5015085848.002559,
                             5331748268.802048, 5585078819.841639],
        state_bytes=[65536, 8454144, 66846720, 134217728],
        decode_step_bytes=[1779636223.9999995, 4448223125.504,
                           6305450531.841639],
        token_flops=[1614741504.0, 1766522880.0, 1815281664.0,
                     1967063040.0, 2017198080.0, 2168979456.0],
        prefill_flops=[208436723712.0, 1756625633280.0, 2712171970560.0],
        param_bytes=6598364160,
        gemv_shapes=[(1536, 1536), (512, 1536), (40, 1536), (1536, 512),
                     (49408, 1536)]),
}

ARCH_CONFIG = {
    "granite-8b": dict(family="dense", n_layers=18, d_model=4096,
                       n_heads=32, n_kv_heads=8, d_head=128, d_ff=14336,
                       vocab=49152, mlp="swiglu", tie_embeddings=False,
                       rope_theta=1e7, norm_eps=1e-5, moe=None),
    "granite-moe-3b-a800m": dict(family="moe", n_layers=32, d_model=1536,
                                 n_heads=24, n_kv_heads=8, d_head=64,
                                 d_ff=512, vocab=49155, mlp="swiglu",
                                 tie_embeddings=True, rope_theta=1e4,
                                 norm_eps=1e-6, moe=(40, 8)),
}


def _cfg(name):
    return registry.config(registry.load_benchmark(), name)


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_are_the_same_numbers(name):
    c, want = _cfg(name), COUNTS[name]
    a = registry.arch(c)
    got = dict(
        decode_weight_bytes=[a.decode_weight_bytes(c, b)
                             for b in range(1, 9)],
        state_bytes=[a.state_bytes(c, n) for n in (1, 129, 1020, 2048)],
        decode_step_bytes=[counts.decode_step_bytes(c, x) for x in (
            [200], [1536, 1100, 257, 2047],
            [1024 + i * 100 for i in range(8)])],
        token_flops=[counts.token_flops(c, n, lg) for n in (1, 1021, 2048)
                     for lg in (False, True)],
        prefill_flops=[counts.prefill_flops(c, n)
                       for n in (128, 1024, 1536)],
        param_bytes=a.param_bytes(c),
        gemv_shapes=a.gemv_shapes(c))
    assert got == want


@pytest.mark.parametrize("name", sorted(ARCH_CONFIG))
def test_program_config_is_the_same(name):
    c, want = _cfg(name), ARCH_CONFIG[name]
    a = registry.arch(c)
    cfg = a.arch_config(c)
    got = {k: getattr(cfg, k) for k in want if k != "moe"}
    got["moe"] = cfg.moe and (cfg.moe.n_experts, cfg.moe.top_k)
    assert (cfg.name, got) == (name, want)
    planner = a.arch_config(c, n_layers=c["serve"]["planner_layers"])
    assert planner.n_layers == {"granite-8b": 36}.get(name, 32)


@pytest.mark.parametrize("arch", [None, "no-such-arch"])
def test_a_config_must_name_an_arch_module(arch):
    c = dict(tiny.TINY, name="nameless")
    if arch is None:
        del c["arch"]
    else:
        c["arch"] = arch
    with pytest.raises(SystemExit, match=r"bench/arch/ has \['decoder'\]"):
        registry.arch(c)


# A second architecture, written as a new file only: it takes the
# decoder's program config, weights and counts, and brings a reference of
# its own.  ``ALTERED`` changes one operation of the reference: the head
# multiplies by the negated weight.
TWIN = '''
import registry

_dec = registry.arch({"arch": "decoder"})
arch_config, gemv_shapes, vocab, schema = (
    _dec.arch_config, _dec.gemv_shapes, _dec.vocab, _dec.schema)
decode_weight_bytes, state_bytes, token_flops, param_bytes = (
    _dec.decode_weight_bytes, _dec.state_bytes, _dec.token_flops,
    _dec.param_bytes)
ALTERED = {altered}


def logits(params, c, tokens, length, quant=None):
    if ALTERED:
        params = dict(params, lm_head=-params["lm_head"])
    return _dec.logits(params, c, tokens, length, quant)
'''

DRIVE = '''
import json, os, sys
sys.path[:0] = [os.path.join(sys.argv[1], "bench"), sys.argv[2]]
import jax
import registry, serve_driver, tiny
assert registry.BENCH == os.path.join(sys.argv[1], "bench")
out = {}
for name in ("twin", "twin-altered"):
    c = dict(tiny.TINY, name=name, arch=name.replace("-", "_"))
    mod = registry.arch(c)
    res = serve_driver.run({"name": "test"}, c, tiny.OPEN, 7, 2.0,
                           tiny.NoTrace(), tiny.clock())
    out[name] = dict(file=mod.__file__, correct=res["correct"],
                     gap=res["checks"]["max_logit_gap"]["value"])
print(json.dumps(out))
'''


def test_a_new_architecture_needs_only_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    for name, altered in (("twin", False), ("twin_altered", True)):
        (root / "bench" / "arch" / f"{name}.py").write_text(
            TWIN.replace("{altered}", str(altered)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", DRIVE, str(root),
                        os.path.dirname(os.path.abspath(__file__))],
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["twin"]["file"] == str(root / "bench" / "arch" / "twin.py")
    assert out["twin"]["correct"], out
    assert not out["twin-altered"]["correct"], out
    assert out["twin-altered"]["gap"] > 10 * out["twin"]["gap"]
    assert {p: p.read_bytes() for p in before} == before


def test_offline_mix_reports_tokens_per_s():
    b = registry.load_benchmark()
    names = {m["name"] for m in registry.end_to_end(b, "granitemoe-batch")}
    assert {"tokens_per_s", "setup_s"} <= names
    res = tiny.serve(mix=tiny.OFFLINE)
    assert res["correct"], res["checks"]
    assert res["e2e"]["tokens_per_s"] > 0
    assert names - {"setup_s"} <= set(res["e2e"])
