"""DeepSeek-V2 as published, through the serving path: YaRN RoPE, the
latent RMSNorm, the mscale^2 softmax scale, raw top-k gates and dropless
routing, against the benchmark's plain float32 reference
(``benchmarks/chip/families/deepseek_v2.py``) at a tiny size."""
from __future__ import annotations

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import weights  # noqa: E402
from cell import load_family  # noqa: E402

from repro.api import Program  # noqa: E402
from repro.configs import get_arch, smoke_variant  # noqa: E402
from repro.models import attention, layers, moe  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.serve.slots import SlotPool, SlotState  # noqa: E402

family = load_family("deepseek_v2")

# the file's keys at tiny widths: E/top_k and the YaRN ramp as published
SIZES = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
         "intermediate_size": 160, "moe_intermediate_size": 24,
         "n_routed_experts": 8, "num_experts_per_tok": 3, "vocab_size": 512}
SEED = 2 ** 40 + 9
P, N = 150, 6                   # prompt, decode steps
MAX_LEN, CHUNK, SLOTS = 192, 64, 4

# float32 XLA against the float32 reference: the program's dispatch and
# combine one-hots carry the gates in bf16 (models/moe.route), 2^-9
# relative; everything else is summation order (1e-6 without it).
XLA_REL = 2e-3
# photonic W8A8 in bf16 against float32: int8 weights and activations at
# width 64 carry about 1% a matmul over some thirty matmuls (read 0.07-0.13)
PHOTONIC_REL = 0.25


def tiny_conf(execution="xla"):
    import json
    conf = json.loads((CHIP / "configs" / "deepseek-v2-lite.json")
                      .read_text())
    conf.update(SIZES, num_hidden_layers=3, execution=execution)
    return conf


def build(execution="xla", dtype="float32", cfg_fn=lambda c: c):
    cfg = family.program_config(tiny_conf(execution))
    cfg = cfg_fn(dataclasses.replace(cfg, compute_dtype=dtype,
                                     param_dtype=dtype))
    params = weights.make_params(tfm.abstract_params(cfg), SEED,
                                 jnp.bfloat16)
    return Program.build(cfg, jax.tree.map(lambda x: x.astype(dtype),
                                           params))


def served(prog, seq, chunk=None, mates=()):
    """Logits at rows P-1 .. P+N-1 of ``seq`` served in slot 0 of a pool:
    prefill (monolithic or in chunks) and N decode steps through the cache,
    each fed the next token of ``seq``; ``mates`` (token arrays) are served
    beside it in the other slots."""
    pool = SlotPool(prog.cfg, SLOTS, MAX_LEN)
    rows = [seq] + list(mates)
    for s in rows:
        slot = pool.allocate(SlotState(rid=len(pool.active_slots()),
                                       prompt_len=P, max_new=N))
        toks = jnp.asarray(s[None, :P])
        if chunk:
            lg, caches = prog.prefill_chunked({"tokens": toks}, MAX_LEN,
                                              chunk)
        else:
            lg, caches = prog.prefill({"tokens": toks}, P)
        pool.write_prefill(slot, caches, P)
        if slot == 0:
            out = [np.asarray(lg[0])]
    for i in range(N):
        t = np.zeros((SLOTS, 1), np.int32)
        for slot, s in enumerate(rows):
            t[slot, 0] = s[P + i]
        lg, pool.caches = prog.decode(jnp.asarray(t), pool.caches,
                                      pool.position_vector())
        out.append(np.asarray(lg[0]))      # read back before advancing
        for slot in range(len(rows)):
            pool.advance(slot)
    return np.stack(out)[:, :SIZES["vocab_size"]].astype(np.float64)


@pytest.fixture(scope="module")
def seq():
    return np.random.default_rng(3).integers(1, SIZES["vocab_size"], P + N,
                                             dtype=np.int32)


@pytest.fixture(scope="module")
def want(seq):
    return np.asarray(family.logits(tiny_conf(), SEED, [seq],
                                    [np.arange(P - 1, P + N)]), np.float64)


def rel(got, want):
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("execution,dtype,tol", [
    ("xla", "float32", XLA_REL), ("photonic", "bfloat16", PHOTONIC_REL)])
@pytest.mark.parametrize("chunk", [None, CHUNK])
def test_served_logits_match_the_reference(seq, want, execution, dtype, tol,
                                           chunk):
    with jax.default_matmul_precision("highest"):
        got = served(build(execution, dtype), seq, chunk)
    assert rel(got, want) < tol
    # the served token is the reference's first choice at every row
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


def _plain_inv_freq(d, theta, rs):
    return (1.0 / theta ** (np.arange(d // 2) / (d // 2))).astype(np.float32)


REMOVED = {
    "yarn": lambda mp: mp.setattr(layers, "yarn_inv_freq", _plain_inv_freq),
    "latent_norm": lambda mp: mp.setattr(attention, "apply_norm",
                                         lambda p, x, *a: x),
    "mscale": lambda mp: mp.setattr(attention, "mla_softmax_scale",
                                    lambda cfg: None),
    "raw_gates": None,
}


@pytest.mark.parametrize("mechanism", list(REMOVED))
def test_each_mechanism_is_needed_to_match(seq, want, mechanism,
                                           monkeypatch):
    """Without any one of them the served logits leave the reference by
    far more than the tolerance that pins them."""
    cfg_fn = lambda c: c
    if REMOVED[mechanism] is None:
        cfg_fn = lambda c: dataclasses.replace(
            c, moe=dataclasses.replace(c.moe, norm_topk_prob=True))
    else:
        REMOVED[mechanism](monkeypatch)
    jax.clear_caches()                  # the jitted cells trace again
    try:
        with jax.default_matmul_precision("highest"):
            got = served(build("xla", "float32", cfg_fn), seq)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert rel(got, want) > 25 * XLA_REL


def test_yarn_inv_freq_closed_form():
    """DeepSeek-V2-Lite's 64 rope dims at theta 1e4, factor 40, original
    length 4096: dims under 10 keep theta^(-2i/64), dims from 23 on take it
    over 40, and dim 16 blends the two 6/13 of the way."""
    cfg = get_arch("deepseek-v2-lite-16b")
    rs = cfg.rope_scaling
    got = layers.yarn_inv_freq(64, 1e4, rs)
    plain = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    r = 6 / 13
    np.testing.assert_allclose(got[16], plain[16] * (r / 40 + 1 - r),
                               rtol=1e-6)
    # and the rope angles the attention applies are these frequencies
    pos = jnp.arange(0, 4096, 97)
    cos, sin = layers.rope_angles(pos, 64, 1e4, rs)
    ang = np.asarray(pos, np.float64)[:, None] * got
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-4)


def test_mscale_squared_softmax_scale():
    """0.1 * 0.707 * ln(40) + 1 = 1.2608, squared 1.5896, over sqrt(192)."""
    cfg = get_arch("deepseek-v2-lite-16b")
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert attention.mla_softmax_scale(cfg) == pytest.approx(
        m * m / math.sqrt(192), rel=1e-12)
    # plain rope: the attention's default 1/sqrt(192)
    assert attention.mla_softmax_scale(
        dataclasses.replace(cfg, rope_scaling=None)) is None


def test_latent_is_normalised_before_it_is_cached():
    """The cached latent of every position has the RMS of the latent
    norm's scale (set to 3 here), whatever the projection's scale."""
    cfg = dataclasses.replace(smoke_variant("deepseek-v2-lite-16b"),
                              compute_dtype="float32")
    p, _ = attention.init_mla(jax.random.PRNGKey(0), cfg)
    p = dict(p, w_dkv=p["w_dkv"] * 50.0,
             kv_norm={"scale": jnp.full_like(p["kv_norm"]["scale"], 3.0)})
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    cache = attention.init_mla_cache(cfg, 2, 16, jnp.float32)
    _, filled = attention.mla_forward(p, cfg, x, cache=cache)
    ckv = np.asarray(filled["ckv"][:, :9])
    np.testing.assert_allclose(np.sqrt((ckv ** 2).mean(-1)), 3.0, rtol=1e-3)


def test_raw_gates_are_the_softmax_probabilities():
    """With norm_topk_prob off, each kept row-expert weight is the router's
    softmax probability itself; on, the top-k weights sum to 1."""
    mcfg = get_arch("deepseek-v2-lite-16b").moe
    mcfg = dataclasses.replace(mcfg, num_experts=8, top_k=3)
    p = {"router": 0.1 * jax.random.normal(jax.random.PRNGKey(0), (16, 8))}
    xg = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 16))
    probs = jax.nn.softmax(xg[0] @ p["router"], axis=-1)
    top = np.sort(np.asarray(probs), axis=-1)[:, -3:]
    _, combine, _ = moe.route(p, xg, mcfg, dropless=True)
    w = np.sort(np.asarray(combine[0].astype(jnp.float32)).sum(-1), -1)
    np.testing.assert_allclose(w[:, -3:], top, rtol=1e-2)
    assert np.all(w[:, -3:].sum(-1) < 0.99)
    _, combine, _ = moe.route(
        p, xg, dataclasses.replace(mcfg, norm_topk_prob=True), dropless=True)
    np.testing.assert_allclose(
        np.asarray(combine[0].astype(jnp.float32)).sum((-2, -1)), 1.0,
        rtol=1e-2)


def test_serving_routes_dropless_and_training_keeps_capacity():
    """Eight rows that all pick the same experts overflow GShard capacity
    (dropped) and none is dropped dropless."""
    mcfg = dataclasses.replace(get_arch("deepseek-v2-lite-16b").moe,
                               num_experts=8, top_k=3, d_ff_expert=8,
                               num_shared=0)
    p, _ = moe.init_moe(jax.random.PRNGKey(0), 16, mcfg)
    x = jnp.ones((8, 1, 16))
    _, aux = moe.apply_moe(p, x, mcfg, dropless=False)
    assert float(aux["dropped_frac"]) > 0.5
    _, aux = moe.apply_moe(p, x, mcfg, dropless=True)
    assert float(aux["dropped_frac"]) == 0.0


@pytest.mark.parametrize("mode", ["prefill", "prefill_chunk", "decode"])
def test_every_served_mode_drops_nothing(monkeypatch, mode):
    cfg = dataclasses.replace(smoke_variant("deepseek-v2-lite-16b"),
                              moe=dataclasses.replace(
                                  smoke_variant("deepseek-v2-lite-16b").moe,
                                  capacity_factor=0.5))
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    seen = []
    real = moe.apply_moe

    def spy(*a, **k):
        y, aux = real(*a, **k)
        seen.append(float(aux["dropped_frac"]))
        return y, aux
    monkeypatch.setattr(moe, "apply_moe", spy)
    B, S, L = 4, 8, 16
    caches = tfm.init_caches(cfg, B, L, jnp.float32)
    toks = jnp.ones((B, 1 if mode == "decode" else S), jnp.int32)
    pos = jnp.arange(B) if mode == "decode" else 0
    with jax.disable_jit():
        tfm.forward(params, cfg, {"tokens": toks}, mode=mode, caches=caches,
                    pos=pos)
    assert seen and all(f == 0.0 for f in seen)


def test_a_request_is_served_alike_alone_and_beside_batch_mates(seq):
    """xla: a request's logits do not depend on what else the decode batch
    holds: no row is dropped, nothing is shared between rows."""
    prog = build("xla", "float32")
    rng = np.random.default_rng(5)
    mates = [rng.integers(1, SIZES["vocab_size"], P + N, dtype=np.int32)
             for _ in range(3)]
    np.testing.assert_array_equal(served(prog, seq),
                                  served(prog, seq, mates=mates))


def test_decode_counts_routing_over_live_slots():
    from repro.serve.batcher import Request
    from repro.serve.scheduler import ContinuousScheduler, expert_loads
    cfg = smoke_variant("deepseek-v2-lite-16b")
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    sched = ContinuousScheduler(Program.build(cfg, params), capacity=4,
                                max_len=32, prefill_bucket=8)
    rng = np.random.default_rng(0)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=rng.integers(1, 200, 5 + i),
                             max_new=4))
    sched.drain()
    s = sched.stats
    layers_moe = moe.num_moe_layers(cfg)
    live = s.useful_steps - s.prompt_tokens       # decode row-steps
    assert s.moe_routed_tokens == live * cfg.moe.top_k * layers_moe
    assert 0 < s.moe_experts_hit <= s.decode_steps * layers_moe * 4
    assert s.moe_max_expert_load * cfg.moe.num_experts >= \
        s.moe_routed_tokens
    assert s.registry.counter("serve.moe_experts_hit").value == \
        s.moe_experts_hit
    route = np.array([[[0, 1], [1, 2], [3, 3]]])      # 1 layer, 3 rows
    np.testing.assert_array_equal(expert_loads(route, [0, 2], 4),
                                  [[1, 1, 0, 2]])


def test_expert_of_a_stacked_bank_is_read_in_place():
    """``BankLayer[e]`` of an (R, E, K, N) bank views the same stack, at
    layer * E + e, and holds exactly expert e of that layer."""
    from repro.core.prepared import BankLayer, prepare_tensor
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 8, 16))
    bank = prepare_tensor(w)
    view = BankLayer(bank, jnp.int32(2))[1]
    assert isinstance(view, BankLayer) and view.shape == (8, 16)
    for f in ("wq", "scale", "wq_t", "scale_t"):
        np.testing.assert_array_equal(getattr(view, f),
                                      getattr(bank, f)[2, 1])


def test_other_moe_presets_keep_their_routing():
    assert get_arch("deepseek-v2-lite-16b").moe.norm_topk_prob is False
    assert smoke_variant("deepseek-v2-lite-16b").moe.norm_topk_prob is False
    for name in ("granite-moe-1b-a400m", "jamba-v0.1-52b"):
        assert get_arch(name).moe.norm_topk_prob is True
        assert smoke_variant(name).moe.norm_topk_prob is True
        assert get_arch(name).rope_scaling is None


def test_named_scopes_are_in_the_compiled_programs():
    from repro import api
    cfg = smoke_variant("deepseek-v2-lite-16b")
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    prog = Program.build(cfg, params, execution="photonic")
    caches = tfm.init_caches(cfg, 2, 16, jnp.float32)
    _, cell = api._decode_cells(False)
    decode = cell.lower(prog.bank, jnp.ones((2, 1), jnp.int32), caches,
                        jnp.array([3, 5]), jax.random.PRNGKey(0),
                        jnp.float32(1.0), cfg=cfg, backend=prog.backend,
                        greedy=True).as_text(debug_info=True)
    for scope in ("moe.route", "moe.dispatch", "moe.experts", "moe.shared",
                  "moe.combine", "mla.absorb", "mla.latent_attn"):
        assert scope in decode, scope
    chunk = api._prefill_chunk_cells(False).lower(
        prog.bank, jnp.ones((1, 8), jnp.int32),
        tfm.init_caches(cfg, 1, 16, jnp.float32), jnp.int32(0),
        jnp.array([7]), cfg=cfg, backend=prog.backend
    ).as_text(debug_info=True)
    for scope in ("mla.up_proj", "moe.route", "moe.experts"):
        assert scope in chunk, scope
