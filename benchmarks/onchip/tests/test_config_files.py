"""A configuration comes to the harness as files alone.

Each directory under ``fixtures/`` holds what a configuration of one of
the program's smoke presets brings: ``configs/<config>.json`` (sizes,
with dotted sizes of sub-config fields, and ``model`` settings, with a
nested moe/mamba/mla override) and ``references/<config>.py`` (``INIT``
rules for the leaves the shared table lacks, and both FLOP functions).
From those files alone each builds, gets weights, passes the sizes check
and gives positive FLOPs.  The two configurations the benchmark runs keep
their weights bit for bit, their FLOP counts and their reference logits.
"""
import copy
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.onchip import driver, flops, reduce, spec, weights
from benchmarks.onchip.references import common
from benchmarks.onchip.tests.smoke import smoke_cell

FIXTURES = spec.HERE / "tests" / "fixtures"
# preset -> (config name, the first leaf the shared table has no rule for)
PRESETS = {"qwen3-moe-30b-a3b-smoke": ("qwen3moe", "router"),
           "deepseek-v3-671b-smoke": ("deepseekv3", "kv_norm"),
           "jamba-1.5-large-398b-smoke": ("jamba15", "a_log"),
           "falcon-mamba-7b-smoke": ("falconmamba", "a_log")}


def _fixture(preset):
    base = FIXTURES / preset
    name = PRESETS[preset][0]
    config = spec.load_json(base / "configs" / f"{name}.json")
    return base, config, spec.reference(name, base=base)


def _ctx(config, base=spec.HERE):
    return dict(config=config, sizes=config["sizes"],
                reference=functools.partial(spec.reference, base=base),
                steps=[(0.0, 3, 40), (1.0, 4, 90)],
                prefills=[(0.0, 2, 24), (1.0, 1, 61)])


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_preset_runs_from_its_files(preset):
    base, config, ref = _fixture(preset)
    assert config["arch"] == preset
    model = driver.build_model(config)
    for group, fields in config["model"].items():
        if isinstance(fields, dict):
            sub = getattr(model.cfg, group)
            assert {f: getattr(sub, f) for f in fields} == fields
    params = weights.make(model, 2**31 + 5, ref.INIT)
    leaves = jax.tree_util.tree_leaves(params)
    assert leaves and all(bool(jnp.isfinite(a).all()) for a in leaves)
    ctx = _ctx(config, base)
    assert reduce.decode_flops(ctx) > 0
    assert reduce.prefill_flops(ctx) > 0
    # the configuration's own count, not flops.py's, which knows no moe,
    # mla or mamba
    one = ref.prefill_flops(config["sizes"], 24)
    assert reduce.prefill_flops({**ctx, "prefills": [(0.0, 2, 24)]}) == \
        2 * one


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_unknown_leaf_names_the_file_for_its_rule(preset):
    base, config, ref = _fixture(preset)
    model = driver.build_model(config)
    leaf = PRESETS[preset][1]
    with pytest.raises(KeyError, match=f"'{leaf}'.*INIT of"):
        weights.make(model, 1)
    rules = {k: v for k, v in ref.INIT.items() if k != leaf}
    with pytest.raises(KeyError, match=f"'{leaf}'.*{ref.__file__}"):
        weights.make(model, 1, rules, where=f"INIT of {ref.__file__}")


def test_dotted_size_is_checked_against_the_sub_config():
    _base, config, _ref = _fixture("jamba-1.5-large-398b-smoke")
    bad = copy.deepcopy(config)
    bad["sizes"]["mamba.d_state"] = 4
    with pytest.raises(ValueError, match="mamba.d_state"):
        driver.build_model(bad)


def test_nested_settings_need_the_sub_config():
    config = copy.deepcopy(smoke_cell("smollm360m.chat").config)
    config["model"]["moe"] = {"n_experts": 8}
    with pytest.raises(ValueError, match="no moe settings"):
        driver.build_model(config)


# sha256 over the leaves of the smoke-size weights, taken with the shared
# table alone, before a configuration could bring rules of its own
WEIGHT_SUMS = [
    ("smollm360m.chat", 7,
     "367a931606119b85f197215fa8a559c4b84f0c04c16e7c5b17206a47744628c3"),
    ("smollm360m.chat", 2**31 + 11,
     "b0713e3721a61a834738bddd31045ab1b109a6526beeb8739ca6033eb17b4f3d"),
    ("mingru360m.longdoc", 7,
     "a74956738d4ebb36234418ad4765757681d6e924c58f26a3b0497f9bb1fd02c4"),
    ("mingru360m.longdoc", 2**31 + 11,
     "e003d0ba28361ccd26e108c0386b5e86ba2d349c66c615b2ade3a3c5267cb4a4"),
]


@pytest.mark.parametrize("name,seed,digest", WEIGHT_SUMS)
def test_weights_of_the_benchmark_configs_unchanged(name, seed, digest):
    cell = smoke_cell(name)
    model = driver.build_model(cell.config)
    ref = spec.reference(cell.config["name"])
    params = weights.make(model, seed, getattr(ref, "INIT", None))
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == digest


# FLOPs of the ctx's steps and prefills at the published sizes, as
# flops.py counted them before a configuration could bring its own
FLOP_COUNTS = {"smollm360m.chat": (89991413760.0, 3672008294400.0),
               "mingru360m.longdoc": (71169146880.0, 3020315566080.0)}


@pytest.mark.parametrize("name", sorted(FLOP_COUNTS))
def test_flops_of_the_benchmark_configs_unchanged(name):
    config = spec.load_cell(name).config
    s = config["sizes"]
    ctx = {**_ctx(config), "steps": [(0.0, 40, 30000), (1.0, 64, 90000)],
           "prefills": [(0.0, 3, 1024), (1.0, 1, 2047)]}
    assert (reduce.decode_flops(ctx), reduce.prefill_flops(ctx)) == \
        FLOP_COUNTS[name]
    assert reduce.decode_flops(ctx) == \
        flops.decode_step(s, s["mixer"], 40, 30000) + \
        flops.decode_step(s, s["mixer"], 64, 90000)


def _stack_before_mixed_layers(params, block, tokens, sizes, quant=None):
    """``common.stack`` as it was when every configuration scanned one
    unit: the defaults of today's helper have to give this bit for bit."""
    x = common.fake_quant(
        params["embed"]["table"][tokens].astype(jnp.float32), quant, -1)
    layers = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    params["unit0"])

    def body(x, p):
        return common.fake_quant(block(x, p), quant, -1), None

    x, _ = jax.lax.scan(body, x, layers)
    return common.rmsnorm(x, params["final_norm"]["scale"],
                          sizes["norm_eps"])


@pytest.mark.parametrize("name", ["smollm360m.chat", "mingru360m.longdoc"])
@pytest.mark.parametrize("quant", [None, "fp8"])
def test_reference_logits_bit_for_bit(name, quant, monkeypatch):
    cell = smoke_cell(name)
    sizes = cell.config["sizes"]
    model = driver.build_model(cell.config)
    params = weights.make(model, 3)
    ref = spec.reference(cell.config["name"])
    tokens = np.arange(40, dtype=np.int32) * 7 % sizes["vocab"]
    rows = np.arange(20, 40, dtype=np.int32)
    now = ref.logits(params, tokens, rows, sizes, quant)
    monkeypatch.setattr(ref, "stack", _stack_before_mixed_layers)
    before = ref.logits(params, tokens, rows, sizes, quant)
    np.testing.assert_array_equal(np.asarray(now), np.asarray(before))


def _marked(n):
    return {"m": jnp.float32(n)}


def test_stack_walks_the_program_order_by_kind():
    """head0, then the pattern (unit0 of kind a, unit1 of kind b) at each
    of two repeats, then tail0; each layer applied in turn, scanned or
    not."""
    d = 4
    emb = {"table": jnp.ones((8, d), jnp.float32)}
    norm = {"scale": jnp.ones((d,), jnp.float32)}
    blocks = {"a": lambda x, p: x * 3.0 + p["m"],
              "b": lambda x, p: x * 2.0 - p["m"]}
    kinds = {"head0": "a", "unit0": "a", "unit1": "b", "tail0": "b"}
    sizes = {"norm_eps": 1e-6}
    order = [("a", 1.0), ("a", 2.0), ("b", 3.0), ("a", 4.0), ("b", 5.0),
             ("b", 6.0)]
    want = jnp.ones((3, d), jnp.float32)
    for kind, m in order:
        want = blocks[kind](want, {"m": m})
    want = common.rmsnorm(want, norm["scale"], 1e-6)
    scanned = {"embed": emb, "final_norm": norm, "head0": _marked(1),
               "unit0": {"m": jnp.array([2.0, 4.0])},
               "unit1": {"m": jnp.array([3.0, 5.0])}, "tail0": _marked(6)}
    one_by_one = {"embed": emb, "final_norm": norm, "head0": _marked(1),
                  "unit0_r0": _marked(2), "unit1_r0": _marked(3),
                  "unit0_r1": _marked(4), "unit1_r1": _marked(5),
                  "tail0": _marked(6)}
    tokens = jnp.array([0, 1, 2])
    for params in (scanned, one_by_one):
        got = common.stack(params, blocks, tokens, sizes, kinds=kinds)
        np.testing.assert_allclose(got, want, rtol=1e-6)


def test_stack_follows_the_program_without_a_scan():
    """A model of one repeat keeps its pattern layers unstacked
    (``unit0_r0``): the reference walks them and agrees with the
    program's forward pass."""
    cell = smoke_cell("smollm360m.chat")
    config = copy.deepcopy(cell.config)
    config["sizes"]["n_layers"] = 1
    config["model"]["n_layers"] = 1
    model = driver.build_model(config)
    params = weights.make(model, 4)
    assert "unit0_r0" in params and "unit0" not in params
    sizes = config["sizes"]
    tokens = np.arange(24, dtype=np.int32) * 5 % sizes["vocab"]
    rows = np.arange(24, dtype=np.int32)
    ref = spec.reference(config["name"])
    with jax.default_matmul_precision("highest"):
        want = model(params, jnp.asarray(tokens)[None])[0, :, :sizes["vocab"]]
        got = ref.logits(params, tokens, rows, sizes)
        skipped = ref.logits({k: v for k, v in params.items()
                              if k != "unit0_r0"}, tokens, rows, sizes)
    # the program computes in bfloat16 (logits here about 0.07, its
    # rounding about 1e-3); a reference that skipped the layer is off by
    # about 0.17
    np.testing.assert_allclose(got, want, atol=5e-3)
    assert np.abs(np.asarray(skipped) - np.asarray(want)).max() > 0.05


def test_head_with_an_untied_table_and_a_stated_scale():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    params = {"embed": {"table": jnp.zeros((16, 8), jnp.float32)},
              "lm_head": {"table": jnp.asarray(rng.normal(size=(16, 8)),
                                               jnp.float32)}}
    sizes = {"d_model": 8, "vocab": 12}
    rows = jnp.array([1, 3])
    got = common.head(params, x, rows, sizes, table="lm_head", scale=0.0625)
    want = np.asarray(x)[[1, 3]] @ np.asarray(params["lm_head"]["table"]).T
    np.testing.assert_allclose(got, want[:, :12] / 16, rtol=1e-6)
