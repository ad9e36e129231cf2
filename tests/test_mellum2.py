"""mellum2-12b on the decomposed-KV path, at a tiny size on the CPU: the
mixed cache (window rings beside factorized full layers), the drop-free
held-expert layer, YaRN, and the serving family's checks of what it
supports."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.engine import DecomposeEngine, EngineConfig
from repro.models import api, moe
from repro.models import decomposed_kv as DK
from repro.models import layers as L
from repro.serving import Engine, Request

#: two periods of the published pattern (8 layers), a 16-row window, YaRN
#: on, 4 of 8 router experts held, top-2 — every width cut, every kind kept
TINY = get_arch("mellum2-12b").replace(
    name="mellum2-tiny", num_layers=8, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=128, vocab=128, num_experts=4,
    router_experts=8, top_k=2, moe_d_ff=32, sliding_window=16,
    yarn_original_max_pos=64, remat=False)
F32 = TINY.replace(dtype="float32")


def _params(cfg, seed=0):
    return api.model_fns(cfg).init(jax.random.PRNGKey(seed), cfg)


def test_layer_kinds_follow_the_published_pattern():
    cfg = get_arch("mellum2-12b")
    assert cfg.layer_kinds == ("window",) * 3 + ("full",) \
        + (("window",) * 3 + ("full",)) * 6
    assert DK.factorized_layers(cfg) == (3, 7, 11, 15, 19, 23, 27)
    assert len(DK.window_layers(cfg)) == 21
    assert cfg.router_width == 64 and cfg.replace(num_experts=16) \
        .router_width == 64


def test_yarn_frequencies_match_the_closed_form():
    """θ 5e5, factor 16 over 8192 positions, β 32/1, head 128: the ramp's
    ends are d·ln(8192 / (2π·β)) / (2 ln θ), floored and ceiled."""
    d, theta = 128, 5e5
    lo = math.floor(d * math.log(8192 / (2 * math.pi * 32))
                    / (2 * math.log(theta)))
    hi = math.ceil(d * math.log(8192 / (2 * math.pi * 1))
                   / (2 * math.log(theta)))
    base = theta ** -(np.arange(0, d, 2) / d)
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0, 1)
    want = base * (1 - ramp) + base / 16 * ramp
    got = np.asarray(L.yarn_frequencies(d, theta, 16.0, 8192, 32.0, 1.0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert (lo, hi) == (18, 35)
    # below the ramp the frequency is θ's own, past it divided by 16
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        base[-1] / 16, rel=1e-6)
    freqs, scale = L.rope_of(get_arch("mellum2-12b"), "full")
    assert scale == pytest.approx(0.1 * math.log(16) + 1)
    freqs_w, scale_w = L.rope_of(get_arch("mellum2-12b"), "window")
    assert scale_w == 1.0
    np.testing.assert_allclose(np.asarray(freqs_w), base, rtol=1e-6)


def _dense_experts(p, x, cfg):
    """The held experts' share written out plainly: every held expert
    over every token, weighted by the renormalized top-k gate."""
    xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    probs = jax.nn.softmax(xf @ p["router"]["w"].astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    top = top / top.sum(-1, keepdims=True)
    g = jnp.zeros_like(probs).at[jnp.arange(xf.shape[0])[:, None],
                                 idx].set(top)
    g = g[:, cfg.expert_first:cfg.expert_first + cfg.num_experts]
    wg, wu, wd = (p[k].astype(jnp.float32)
                  for k in ("w_gate", "w_up", "w_down"))
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, wg)) \
        * jnp.einsum("td,edf->etf", xf, wu)
    return jnp.einsum("te,etd->td", g, jnp.einsum("etf,efd->etd", h, wd))


@pytest.mark.parametrize("held", [2, 4])
def test_the_shares_sum_to_the_uncut_layer(held):
    """8 router experts cut into 8 / held shares: each share's output
    (the experts it holds, routed over all 8) adds up to the layer that
    holds all 8, and each share agrees with the plain dense sum."""
    whole = F32.replace(num_experts=8, expert_first=0)
    p = moe.moe_ffn_init(jax.random.PRNGKey(3), whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, whole.d_model))
    full, _, _ = moe.held_experts_ffn(p, x, whole)
    total = jnp.zeros_like(full)
    for first in range(0, 8, held):
        cfg = F32.replace(num_experts=held, expert_first=first)
        share = dict(p, **{k: p[k][first:first + held]
                           for k in ("w_gate", "w_up", "w_down")})
        y, _, _ = moe.held_experts_ffn(share, x, cfg)
        np.testing.assert_allclose(
            np.asarray(y).reshape(-1, whole.d_model),
            np.asarray(_dense_experts(share, x, cfg)), rtol=1e-5,
            atol=1e-5)                 # float32 sums in another order
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_no_token_is_dropped_by_its_batch():
    """A token's expert output is the same alone and batched with others:
    with every token routed to one expert, a capacity buffer would drop
    most of them; the held layer drops none."""
    cfg = TINY
    p = moe.moe_ffn_init(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 16, cfg.d_model)
                          ).astype(cfg.jax_dtype)
    batched, _, _ = moe.held_experts_ffn(p, x, cfg)
    for b in range(4):
        alone, _, _ = moe.held_experts_ffn(p, x[b:b + 1], cfg)
        np.testing.assert_array_equal(np.asarray(alone[0], np.float32),
                                      np.asarray(batched[b], np.float32))
    skew = dict(p, router={"w": p["router"]["w"].at[:, 0].add(50.0)})
    y, _, picks = moe.held_experts_ffn(skew, jnp.abs(x), cfg)
    assert (np.asarray(picks)[:, 0] == 0).all()
    assert bool(jnp.all(jnp.abs(y.astype(jnp.float32)).sum(-1) > 0))


@pytest.mark.parametrize("batch", ["spread", "one_token", "all_away"])
@pytest.mark.parametrize("layer", [0, 3, 7])
def test_the_stacked_call_is_the_layers_own(layer, batch):
    """Handed every layer's expert weights as one stack and a traced layer
    index, the held layer computes what the one-layer call computes on
    that layer's weights: with picks on every held expert, with one token
    (held experts that get no pick), and with every pick away."""
    cfg, n = F32, F32.num_layers
    per = [moe.moe_ffn_init(jax.random.PRNGKey(10 + i), cfg)
           for i in range(n)]
    p = per[layer]
    shape = (1, 1) if batch == "one_token" else (4, 16)
    x = jax.random.normal(jax.random.PRNGKey(11), shape + (cfg.d_model,))
    if batch == "all_away":            # experts 4 and 5 are held elsewhere
        p = dict(p, router={"w": p["router"]["w"].at[:, 4:6].add(50.0)})
        x = jnp.abs(x)
    stacks = {w: jnp.stack([q[w] for q in per])
              for w in ("w_gate", "w_up", "w_down")}
    assert stacks["w_gate"].shape == (n, cfg.num_experts, cfg.d_model,
                                      cfg.moe_d_ff)
    stacked = jax.jit(lambda p, x, i: moe.held_experts_ffn(p, x, cfg, i))
    got, aux, picks = stacked(dict(p, **stacks), x, jnp.int32(layer))
    want, aux1, picks1 = moe.held_experts_ffn(p, x, cfg)
    held = np.unique(np.asarray(picks)[np.asarray(picks) < cfg.num_experts])
    assert len(held) == {"spread": 4, "one_token": 1, "all_away": 0}[batch]
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(picks1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(aux), float(aux1), rtol=1e-6)
    assert (np.asarray(got) == 0).all() == (batch == "all_away")


#: a dense decoder at the same tiny widths, float32: one period ("full",)
DENSE_F32 = get_arch("granite-3-2b").replace(
    name="dense-tiny", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=128, vocab=128, remat=False,
    dtype="float32")


@pytest.mark.parametrize("cfg", [F32, DENSE_F32], ids=["mellum", "dense"])
def test_full_rank_decomposed_path_is_the_forward(cfg):
    """At full rank (exact SVD) the decomposed path is the model's own
    forward, for a model of mixed layer kinds and for a dense one: prefill
    24 rows, then decode 20 tokens, so a 16-row window ring is overwritten
    more than once."""
    p = _params(cfg)
    fns = api.model_fns(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 44), 1, cfg.vocab)
    full = fns.forward(p, cfg, toks)
    if isinstance(full, tuple):          # an expert model adds its aux loss
        full = full[0]
    lg, cache, *picks = DK.prefill_dkv(p, cfg, toks[:, :24], rank=24,
                                       tail=32, exact=True)
    if cfg.sliding_window:
        assert cache["ring"]["k"].shape == (6, 2, 16, 2, 16)
        assert cache["k_u"].shape[0] == 2
        assert int(picks[0].sum()) == 2 * 24 * 2 * 8
    else:
        assert "ring" not in cache and picks == []
        assert cache["k_u"].shape[0] == cfg.num_layers
    # float32 throughout: only summation order separates the two paths
    np.testing.assert_allclose(lg, full[:, 23], atol=1e-4)
    for t in range(24, 44):
        lg, cache = DK.decode_step_dkv(p, cfg, toks[:, t], cache,
                                       jnp.full((2,), t, jnp.int32), 24)
        np.testing.assert_allclose(lg, full[:, t], atol=1e-4)


def test_window_masks_what_lies_outside():
    """Only positions inside the window reach a window layer: with every
    attention output but the first (window) layer's zeroed — expert MLPs
    mix no positions — the last logits ignore a token 34 rows back and
    follow one 9 rows back."""
    cfg = F32
    p = _params(cfg)
    p["layers"]["attn"]["wo"]["w"] = p["layers"]["attn"]["wo"]["w"] \
        .at[1:].set(0.0)
    fns = api.model_fns(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 1, cfg.vocab)
    last = lambda t: np.asarray(fns.forward(p, cfg, t)[0][0, -1])
    bump = lambda i: toks.at[0, i].set((toks[0, i] + 1) % cfg.vocab)
    np.testing.assert_array_equal(last(toks), last(bump(5)))
    assert np.abs(last(toks) - last(bump(30))).max() > 1e-3


def _engine(cfg, params, **kw):
    de = DecomposeEngine(EngineConfig(kv_rank=8, kv_tail=16, sched_bucket=16,
                                      sched_max_admit=2, decode_block=4))
    return Engine(cfg, params, slots=2, max_len=80, decompose_engine=de,
                  **kw)


def test_engine_serves_and_counts_the_mixed_cache():
    p = _params(TINY)
    eng = _engine(TINY, p)
    rng = np.random.default_rng(0)
    lens = [20, 40, 33]
    reqs = [Request(uid=i, prompt=rng.integers(1, 128, n, dtype=np.int32),
                    max_new_tokens=24) for i, n in enumerate(lens)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and len(r.out_tokens) == 24 for r in reqs)
    assert eng.stats.tail_folds > 0          # answers outgrow the tail
    got = {(m.name, tuple(m.labels.items())): m.value
           for m in eng.obs.registry.metrics() if hasattr(m, "value")}
    assert got[("serving_dkv_layers", (("kind", "factorized"),))] == 2
    assert got[("serving_dkv_layers", (("kind", "window"),))] == 6
    held = got[("serving_moe_assignments_total", (("share", "held"),))]
    away = got[("serving_moe_assignments_total", (("share", "away"),))]
    # every real prompt token picks top_k experts in every layer
    assert held + away == sum(lens) * 2 * 8 and held > 0 and away > 0


def test_paged_refuses_a_windowed_config():
    with pytest.raises(ValueError, match="served on the slab"):
        _engine(TINY, _params(TINY), paged=True)


def test_decomposed_kv_checks_what_it_supports():
    assert DK.unsupported(TINY) is None
    assert DK.unsupported(get_arch("granite-3-2b")) is None
    ssm = get_arch("mamba2-780m").reduced()
    with pytest.raises(ValueError, match="decomposed execution"):
        api.decomposed_fns(ssm, DecomposeEngine(EngineConfig(kv_rank=4)))
    olmoe = get_arch("olmoe-1b-7b").reduced()
    assert "drop-free" in DK.unsupported(olmoe)
    with pytest.raises(ValueError, match="decomposed KV"):
        _engine(olmoe, _params(olmoe))
    fns = api.decomposed_fns(TINY, DecomposeEngine(EngineConfig(kv_rank=4)))
    lg, cache, picks = fns.prefill_dkv(_params(TINY), jnp.ones((1, 24),
                                                               jnp.int32))
    assert lg.shape == (1, TINY.padded_vocab) and "ring" in cache
