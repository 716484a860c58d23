"""The correctness check must fail where the program is wrong: its
control (the configuration's lower precision) and each fault a cell
can have, planted under a full run of a small cell.  Limits here are
the small cells' own, set from their readings as the cells' limits
are: sound runs read a widest logit gap of 0.013-0.035, the A4
control 0.27."""
import json
import os

import jax.numpy as jnp
import pytest

import benchtest

TINY_LIMIT = 0.1


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    cfg = benchtest.tiny_decoder()
    cfg["checks"]["max_logit_gap"] = TINY_LIMIT
    with open(os.path.join(benchtest.BENCH, "configs",
                           "ultranet-416.json")) as f:
        u = json.load(f)
    u["frame"] = 64
    u["checks"]["min_frames_compared"] = 2
    return benchtest.make_root(
        tmp_path_factory.mktemp("checks"),
        configs={"tiny-dec": cfg, "tiny-u": u,
                 "tiny-mem": benchtest.tiny_decoder("granite-8b-d9-mem")},
        mixes={"tiny_mix": benchtest.TINY_MIX},
        cells=[("tiny.decode", "tiny-dec", "tiny_mix"),
               ("tiny.mem", "tiny-mem", "tiny_mix"),
               ("tiny.stream", "tiny-u", "frame_stream")])


def test_sound_decode_is_correct(root):
    r = benchtest.run(root, "tiny.decode")
    assert r["correct"], r["checks"]
    assert r["checks"]["max_logit_gap"]["value"] < TINY_LIMIT


def test_decode_control_fails(root):
    r = benchtest.run(root, "tiny.decode", control=True)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_memory_control_mechanics(root):
    """The memory route's control is the reference at int8 activations
    in the program's place: its own first choices are what the
    reference scores, and the number compared is the mean gap.  On the
    chip it reads about 10x the program (PERF.md); on the CPU the
    program's bf16 arithmetic differs from the TPU's enough that the
    two overlap at this size, so only the mechanics are checked."""
    r = benchtest.run(root, "tiny.mem", control=True)
    assert list(r["checks"]) == ["mean_logit_gap", "tokens_compared"]
    w = r["window"]
    assert w["mean_logit_gap"] == r["checks"]["mean_logit_gap"]["value"]
    assert 0 <= w["off_first_choice"] <= 1
    assert w["max_logit_gap"] >= w["mean_logit_gap"] >= 0


def _token_altered(logits, cache):
    last = logits[:, -1, :]
    wrong = (last.argmax(-1) + 1) % last.shape[-1]
    bump = jnp.zeros_like(last).at[0, wrong[0]].set(1e3)
    return logits.at[:, -1, :].add(bump), cache


def _state_unchanged(logits, cache, old):
    return logits, old


def _half_batch(logits, cache):
    half = logits.shape[0] // 2
    return logits.at[half:].set(0.0), cache


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_decode_faults_fail(root, monkeypatch, fault):
    import repro.models as M
    real = M.decode_step

    def broken(cfg, params, cache, tokens, advance=None):
        logits, new = real(cfg, params, cache, tokens, advance=advance)
        if fault == "token_altered":
            return _token_altered(logits, new)
        if fault == "state_unchanged":
            return _state_unchanged(logits, new, cache)
        return _half_batch(logits, new)

    monkeypatch.setattr(M, "decode_step", broken)
    r = benchtest.run(root, "tiny.decode")
    assert not r["correct"], (fault, r["checks"])


def test_stream_sound_control_and_altered_answer(root, monkeypatch):
    r = benchtest.run(root, "tiny.stream")
    assert r["correct"], r["checks"]
    assert r["checks"]["frames_compared"]["value"] >= 2
    c = benchtest.run(root, "tiny.stream", control=True)
    assert not c["correct"]
    assert c["checks"]["mismatched_values"]["value"] > 0
    from repro.models import ultranet as U
    real = U.ultranet_forward
    monkeypatch.setattr(U, "ultranet_forward", lambda *a, **k:
                        real(*a, **k).at[0, 0, 0, 0].add(1))
    f = benchtest.run(root, "tiny.stream")
    assert not f["correct"]
    assert f["checks"]["mismatched_values"]["value"] > 0
