"""granite-4.0-h-small (moe_hybrid) at a small size on the CPU: the
program against the plain float32 reference (``reference_hybrid``),
chunked prefill and cached decode against the full forward, the
per-slot state contract (advance mask, slot reset), the dropless MoE
layer, layer-at-a-time packing and serving through the engine.

Tolerances: the program keeps activations in bfloat16 (8 mantissa
bits, a relative rounding of 2**-9 per value) and the reference in
float32, over three layers and a head.  The logits are divided by the
configuration's logits_scaling (16), so they lie within about +-0.1;
where the comparison is against the reference, it allows 5% of the
largest logit, about 20 times the bfloat16 step at that size.  The
int8 KV cache of the served path adds a rounding of 1/254 of each
row's largest key and value.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.models import (LayerStream, Rules, decode_step, forward,
                          init_cache, init_params, prefill_slot,
                          reset_slot, serve_params, values)
from repro.models import layers as L
from repro.models import reference_hybrid

RULES = Rules(tp=None, fsdp=None, ep=None, batch=())


def _realistic_ssm(params, key):
    """Mamba2's own init for the SSM scalars (A in [1, 16], dt in
    [1e-3, 1e-1]) instead of the zeros of ``ssm_init``, so the state
    decays at the rates a trained model's does."""
    out = jax.tree_util.tree_map(lambda a: a, params)
    for name, blk in out["blocks"].items():
        if "mamba" not in blk:
            continue
        m = blk["mamba"]
        k1, k2, key = jax.random.split(key, 3)
        shape = m["a_log"].shape
        m["a_log"] = jnp.log(jax.random.uniform(
            k1, shape, jnp.float32, minval=1.0, maxval=16.0))
        dt = jnp.exp(jax.random.uniform(k2, shape, jnp.float32,
                                        minval=np.log(1e-3),
                                        maxval=np.log(1e-1)))
        m["dt_bias"] = (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32)
    return out


@pytest.fixture(scope="module")
def model():
    cfg = get_arch("granite-4.0-h-small").reduced()
    params = values(init_params(cfg, RULES, jax.random.PRNGKey(3)))
    return cfg, _realistic_ssm(params, jax.random.PRNGKey(4))


@pytest.fixture(scope="module")
def programs(model):
    cfg, _ = model
    dec = jax.jit(lambda p, c, t, adv: decode_step(cfg, p, c, t,
                                                   advance=adv))
    pre = jax.jit(lambda p, c, s, t, nv: prefill_slot(cfg, p, c, s, t, nv))
    return dec, pre


def _tokens(cfg, n, seed):
    return np.random.default_rng(seed).integers(0, cfg.vocab, n).astype(
        np.int32)


def _close(got, want):
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=0.05 * scale)


def test_reduced_keeps_both_mixers_and_experts(model):
    cfg, _ = model
    assert cfg.layer_types == ("mamba", "attention", "mamba")
    assert cfg.n_experts > cfg.top_k > 1
    full = get_arch("granite-4.0-h-small")
    assert len(full.layer_period) == 10 and full.layer_period[5] == \
        "attention"


def test_forward_matches_reference(model):
    cfg, params = model
    toks = np.stack([_tokens(cfg, 12, 0), _tokens(cfg, 12, 1)])
    got = np.asarray(forward(cfg, params, {"tokens": jnp.asarray(toks)},
                             diff=False), np.float32)
    want = reference_hybrid.forward(cfg, params, toks)
    _close(got, want)


def test_chunked_prefill_then_decode_matches_reference(model, programs):
    """A 10-token prompt prefilled in chunks of 4 (the last padded to
    2 of 4), then 5 tokens decoded through the cache: every position's
    logits against the reference's full forward over the same 15."""
    cfg, params = model
    dec, pre = programs
    seq = _tokens(cfg, 15, 2)
    prompt = 10
    cache = values(init_cache(cfg, RULES, 2, 32))
    for start in range(0, prompt - 1, 4):
        n = min(4, prompt - 1 - start)
        chunk = np.zeros((1, 4), np.int32)
        chunk[0, :n] = seq[start:start + n]
        cache = pre(params, cache, 1, jnp.asarray(chunk),
                    jnp.asarray([n], np.int32))
    assert np.asarray(cache["index"]).tolist() == [0, prompt - 1]
    got = []
    for t in range(prompt - 1, len(seq)):
        toks = np.zeros((2, 1), np.int32)
        toks[1, 0] = seq[t]
        logits, cache = dec(params, cache, jnp.asarray(toks),
                            jnp.asarray([0, 1], np.int32))
        got.append(np.asarray(logits[1, 0], np.float32))
    want = reference_hybrid.forward(cfg, params, seq[None])[0]
    _close(np.stack(got), want[prompt - 1:])


def test_advance_zero_keeps_slot_state_bit_identical(model, programs):
    cfg, params = model
    dec, pre = programs
    cache = values(init_cache(cfg, RULES, 2, 32))
    for slot, seed in ((0, 5), (1, 6)):
        cache = pre(params, cache, slot,
                    jnp.asarray(_tokens(cfg, 4, seed)[None]),
                    jnp.asarray([4], np.int32))
    toks = jnp.asarray(_tokens(cfg, 2, 7)[:, None])
    _, new = dec(params, cache, toks, jnp.asarray([1, 0], np.int32))
    for name in ("k", "v", "k_scale", "v_scale", "conv", "ssm"):
        before, after = np.asarray(cache[name]), np.asarray(new[name])
        np.testing.assert_array_equal(after[:, 1], before[:, 1])
        assert not np.array_equal(after[:, 0], before[:, 0]), name
    assert np.asarray(new["index"]).tolist() == [5, 4]


def test_reset_then_join_equals_a_fresh_slot(model, programs):
    """Slot 1 served another request, was reset and took a new prompt;
    its state and next logits equal those of a slot 1 that was never
    used, beside the same slot 0."""
    cfg, params = model
    dec, pre = programs
    four = jnp.asarray([4], np.int32)
    step = jnp.asarray(_tokens(cfg, 2, 9)[:, None])

    def prefill(cache, slot, seed):
        return pre(params, cache, slot,
                   jnp.asarray(_tokens(cfg, 4, seed)[None]), four)

    used = prefill(prefill(values(init_cache(cfg, RULES, 2, 32)), 0, 10),
                   1, 11)
    _, used = dec(params, used, step, jnp.asarray([1, 1], np.int32))
    used = prefill(reset_slot(used, 1), 1, 8)

    fresh = prefill(values(init_cache(cfg, RULES, 2, 32)), 0, 10)
    _, fresh = dec(params, fresh, step, jnp.asarray([1, 0], np.int32))
    fresh = prefill(fresh, 1, 8)
    for name in used:
        np.testing.assert_array_equal(np.asarray(used[name]),
                                      np.asarray(fresh[name]), name)
    la, _ = dec(params, used, step, jnp.asarray([1, 1], np.int32))
    lb, _ = dec(params, fresh, step, jnp.asarray([1, 1], np.int32))
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _moe_setup(model):
    cfg, params = model
    mcfg = L.MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                       n_experts=cfg.n_experts, top_k=cfg.top_k,
                       shared_expert=True, shared_d_ff=cfg.shared_d_ff)
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["l0"]["moe"])
    return cfg, mcfg, p


def _moe_loop(cfg, p, x):
    b, s, d = x.shape
    y = reference_hybrid.moe(jax.tree_util.tree_map(np.asarray, p),
                             np.asarray(x, np.float32).reshape(b * s, d),
                             cfg)
    return y.reshape(b, s, d)


def test_dropless_moe_equals_a_loop_over_chosen_experts(model):
    cfg, mcfg, p = _moe_setup(model)
    x = jax.random.normal(jax.random.PRNGKey(12), (3, 5, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    y, stats = jax.jit(lambda p, x: L.moe_dropless(p, mcfg, x))(p, x)
    _close(np.asarray(y, np.float32), _moe_loop(cfg, p, x))
    rows, peak, touched = np.asarray(stats).tolist()
    assert rows == 15 * cfg.top_k and peak <= 15 and touched <= cfg.n_experts


def test_dropless_moe_keeps_every_row_when_all_pick_one_expert(model):
    """Every token's router logit for expert 0 dominates, so experts 0
    and 1 get every token: capacity dispatch (1.25 x T x k / E slots
    an expert) drops most rows, the dropless layer none."""
    cfg, mcfg, p = _moe_setup(model)
    router = np.zeros((cfg.d_model, cfg.n_experts), np.float32)
    router[:, 0], router[:, 1] = 4.0, 2.0
    p = dict(p, router={"kernel": jnp.asarray(router, jnp.bfloat16)})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(13),
                                  (2, 8, cfg.d_model))).astype(jnp.bfloat16)
    y, stats = jax.jit(lambda p, x: L.moe_dropless(p, mcfg, x))(p, x)
    want = _moe_loop(cfg, p, x)
    _close(np.asarray(y, np.float32), want)
    assert np.asarray(stats).tolist() == [16 * cfg.top_k, 16, cfg.top_k]
    capped = np.asarray(L.moe_apply(p, mcfg, x), np.float32)
    assert np.abs(capped - want).max() > 0.2 * np.abs(want).max()


def test_layer_at_a_time_packing_is_bitwise_the_whole_tree(model):
    cfg, params = model
    whole = serve_params(params, bits=4, min_size=1024, compute="memory")
    shared = {k: v for k, v in params.items() if k != "blocks"}
    stream = LayerStream(
        shared=shared, names=tuple(params["blocks"]), depth=1,
        make=lambda name, i: jax.tree_util.tree_map(
            lambda a: a[i], params["blocks"][name]))
    byl = serve_params(stream, bits=4, min_size=1024, compute="memory")
    la, ta = jax.tree_util.tree_flatten(whole)
    lb, tb = jax.tree_util.tree_flatten(byl)
    assert ta == tb
    for a, b in zip(la, lb):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layer_stream_over_two_periods_is_bitwise_the_whole_tree():
    cfg = dataclasses.replace(get_arch("granite-4.0-h-small").reduced(),
                              n_layers=6,
                              layer_types=("mamba", "attention", "mamba") * 2)
    params = values(init_params(cfg, RULES, jax.random.PRNGKey(14)))
    whole = serve_params(params, bits=4, min_size=1024, compute="memory")
    stream = LayerStream(
        shared={k: v for k, v in params.items() if k != "blocks"},
        names=tuple(params["blocks"]), depth=2,
        make=lambda name, i: jax.tree_util.tree_map(
            lambda a: a[i], params["blocks"][name]))
    byl = serve_params(stream, bits=4, min_size=1024, compute="memory")
    la, ta = jax.tree_util.tree_flatten(whole)
    lb, tb = jax.tree_util.tree_flatten(byl)
    assert ta == tb
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="memory"):
        serve_params(stream, compute="sdv")


def test_engine_serves_bit_exact_with_joins_and_counts_moe(model):
    """Requests served together, with mid-wave joins and 4-token
    prefill chunks, give the tokens each gives served alone."""
    from repro.serving import BucketShape, Engine
    cfg, params = model
    reqs = [(tuple(_tokens(cfg, n, 20 + n)), k)
            for n, k in ((9, 5), (3, 2), (6, 4), (11, 3))]

    def serve(batch):
        eng = Engine(cfg, params, compute="memory", prefill_chunk=4,
                     buckets=(BucketShape(2, 32),), plan_policy="default")
        rids = [eng.submit(p, k) for p, k in batch]
        while eng.depth():
            eng.step(force=True)
        out = {c.rid: c.tokens for c in eng.completions}
        return [out[r] for r in rids], eng

    together, eng = serve(reqs)
    assert eng.prefill_chunk == 4
    m = eng.metrics
    assert m.prefill_chunks > 0 and m.midwave_joins > 0
    assert m.moe_calls > 0 and m.moe_rows % cfg.top_k == 0
    assert m.moe_peak_rows <= m.moe_rows
    assert 1.0 <= m.snapshot()["moe"]["load_peak"] <= cfg.n_experts
    for req, got in zip(reqs, together):
        alone, _ = serve([req])
        assert alone[0] == got


def test_moe_family_decode_matches_forward_on_the_dropless_path():
    """The moe family now serves dropless too: chunked prefill and
    cached decode against the inference forward, both dropless.  Both
    run the program's bfloat16 arithmetic; the decode path alone adds
    the int8 KV cache, so the tolerance is the reference comparisons'
    (5% of the largest logit).  (llama4-maverick's interleaved decode
    ignores the int8 cache's scales and never matched its forward; it
    is left out.)"""
    cfg = get_arch("phi3.5-moe").reduced()
    params = values(init_params(cfg, RULES, jax.random.PRNGKey(15)))
    seq = _tokens(cfg, 12, 16)
    full = np.asarray(forward(cfg, params, {"tokens": jnp.asarray(seq[None])},
                              diff=False)[0], np.float32)
    cache = values(init_cache(cfg, RULES, 1, 16))
    cache, stats = prefill_slot(cfg, params, cache, 0,
                                jnp.asarray(seq[None, :8]),
                                jnp.asarray([8], np.int32), moe_stats=True)
    assert np.asarray(stats)[:, 0].tolist() == \
        [8 * cfg.top_k] * (cfg.n_layers // cfg.moe_every)
    got = []
    for t in range(8, 12):
        logits, cache = decode_step(cfg, params, cache,
                                    jnp.asarray(seq[None, t:t + 1]))
        got.append(np.asarray(logits[0, 0], np.float32))
    _close(np.stack(got), full[8:])
