"""A run with the timed path broken underneath must come out not correct.

Each test drives the rest of a run (``harness.run_cell``: set-up, window,
check) past the harness's look for a chip, on the CPU at a tiny size,
with one fault planted in the program's decode step, and sees ``correct``
false.  The exchange between chips has no fault to plant: every cell runs
on one chip."""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from harness import run_cell
from tiny import tiny_cell

# Widest gap a sound tiny run reads is 0.239 over ten seeds of each
# configuration (CPU); the int4 control reads 1.68 or more.
TINY_LIMIT = 0.6


def _run(name="deepseek-7b", seed=31):
    return run_cell(tiny_cell(name, limit=TINY_LIMIT), seed, 1.5, False,
                    time.perf_counter())


def _patch_decode(monkeypatch, fault):
    from repro import api
    orig = api.Program.decode_sample

    def broken(self, tokens, caches, pos, key=None, temperature=0.0):
        tok, new = orig(self, tokens, caches, pos, key=key,
                        temperature=temperature)
        return fault(tok, caches, new)
    monkeypatch.setattr(api.Program, "decode_sample", broken)


def test_sound_run_is_correct():
    assert _run()["correct"]


@pytest.mark.parametrize("name", ["deepseek-7b", "deepseek-7b-rb"])
def test_state_left_unchanged(monkeypatch, name):
    _patch_decode(monkeypatch, lambda tok, old, new: (tok, old))
    assert not _run(name)["correct"]


def test_half_the_batch_left_out(monkeypatch):
    def fault(tok, old, new):
        h = tok.shape[0] // 2
        return jnp.concatenate([tok[:h], tok[:tok.shape[0] - h]]), new
    _patch_decode(monkeypatch, fault)
    assert not _run()["correct"]


def test_token_altered_where_produced(monkeypatch):
    _patch_decode(monkeypatch, lambda tok, old, new: ((tok + 1) % 512, new))
    assert not _run()["correct"]


@pytest.mark.parametrize("name", ["deepseek-7b", "deepseek-7b-rb"])
def test_control_fails_the_limit(name):
    """The control, the reference with int4 weight matmuls in the
    program's place, reads above the limit that sound runs stay under."""
    r = run_cell(tiny_cell(name, limit=TINY_LIMIT), 47, 1.5, False,
                 time.perf_counter(), control_bits=4)
    assert r["correct"]
    assert r["control"]["max_logit_gap"] > TINY_LIMIT


def test_sample_takes_a_request_from_every_slot():
    """The comparison reads the longest request and one from each slot, so
    a fault confined to any part of the decode batch reaches it."""
    import types

    import numpy as np

    import check
    done = {rid: types.SimpleNamespace(
        rid=rid, prompt_len=4, tokens=np.zeros(4 + 1 + rid % 7, np.int32))
        for rid in range(40)}
    slot_of = {rid: rid % 8 for rid in done}
    for seed in (1, 2, 2 ** 33 + 5):
        comps = check.sample(done, slot_of, seed)
        assert comps[0].rid == 6              # the longest, lowest rid
        assert check.slots_covered(comps[1:], slot_of) == 8
        assert len(comps) == 9
