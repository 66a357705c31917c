"""Granite 4.0-H's mechanisms on the CPU at the SMOKE size: the engine
against the plain reference, the expert layer's held shares and dropless
routing, the hybrid layer pattern, and the engine's spans."""
import dataclasses
import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models.common import init_params
from repro.models.layers import mlp
from repro.models.moe import moe, moe_specs
from repro.serve.engine import Engine, ServeConfig

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "bench"))
from harness import hybrid_lm, hybrid_serving  # noqa: E402

ARCH = "granite-4.0-h-small"


def smoke(**kw):
    return dataclasses.replace(C.get(ARCH, smoke=True), **kw)


def reference_config(arch) -> dict:
    """The reference's configuration (published keys) of a program arch."""
    return dict(hybrid_serving.arch_view(arch), vocab=arch.vocab)


@pytest.mark.parametrize("prompt", [19, 24])
def test_engine_prefill_then_decode_matches_the_reference(prompt):
    """Prefill (chunked SSD over 3 chunks of 8, dropless experts) and then
    decode through the cache (the recurrent step, K/V appended) give the
    reference's logits at every position.  Both run in float32; the
    program sums the SSM in chunks and the reference token by token, and
    each routes in its own order, so they agree to float32 rounding
    carried through 6 layers: 1e-5 of the logits' own size (6e-7 read;
    bfloat16 compute reads about 1e-2)."""
    arch = smoke(compute_dtype="float32")
    cfg = reference_config(arch)
    params = hybrid_lm.make_params(cfg, 11)
    engine = Engine(arch, params=params)
    rng = np.random.default_rng(prompt)
    new = 6
    toks = rng.integers(2, arch.vocab, prompt + new).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(engine.score(toks[None, :prompt],
                                      toks[None, prompt:-1]))[0]
    want = np.asarray(hybrid_lm.forward_logits(
        cfg, params, jnp.asarray(toks),
        jnp.arange(prompt - 1, prompt + new - 1)))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    # greedy generation follows the scored logits
    out = engine.generate(toks[None, :prompt],
                          ServeConfig(max_new_tokens=2, eos_id=-1))
    assert out[0, prompt] == got[0].argmax()
    # every slot of the prefill and the decode steps routed, none dropped
    held, routed, dropped = engine.slots.values()
    assert routed == arch.n_layers * arch.top_k * (prompt + 1)
    assert 0 < held < routed and dropped == 0


def test_held_shares_add_up_to_the_uncut_layer():
    """Each chip's expert layer, told which experts it holds, computes its
    share of the routed sum and the shared expert; over all shares, the
    routed parts plus the shared expert counted once are the uncut layer."""
    whole = smoke(compute_dtype="float32", experts_held=0, expert_first=0)
    p = init_params(moe_specs(whole), jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, whole.d_model))
    want, _, _ = moe(p, x, whole, dropless=True)
    held = 2
    shares = []
    for first in range(0, whole.n_experts, held):
        cut = dataclasses.replace(whole, experts_held=held,
                                  expert_first=first)
        mine = dict(p, **{k: p[k][first:first + held]
                          for k in ("wg", "wu", "wd")})
        y, _, slots = moe(mine, x, cut, dropless=True)
        shares.append((y, slots))
    n = len(shares)
    shared = mlp(p["shared"], x, whole)
    total = sum(y for y, _ in shares) - (n - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the held slots of the shares partition the routed ones
    routed = 2 * 16 * whole.top_k
    assert sum(int(s[0]) for _, s in shares) == routed
    assert all(int(s[1]) == routed for _, s in shares)


def test_skewed_router_is_dropless_in_prefill():
    """A router that sends every token to one expert overflows the training
    capacity; the serving prefill keeps every slot and equals the layer
    applied token by token."""
    arch = smoke(compute_dtype="float32")
    p = init_params(moe_specs(arch), jax.random.PRNGKey(5))
    hot = arch.expert_first + 1           # a held expert
    p["router"] = p["router"].at[:, hot].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, arch.d_model))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    p["router"] = p["router"].at[0, hot].set(100.0)   # every token's top 1
    got, _, slots = moe(p, x, arch, dropless=True)
    per_token = jnp.concatenate(
        [moe(p, x[:, t:t + 1], arch)[0] for t in range(x.shape[1])], axis=1)
    # float32 sums over the experts in another order: 1e-6 of the scale
    scale = float(jnp.abs(per_token).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(per_token),
                               atol=1e-6 * scale)
    assert int(slots[2]) == 0
    _, _, capped = moe(p, x, arch)        # the training capacity drops
    assert int(capped[2]) > 0


def test_layer_pattern_puts_experts_everywhere_when_every_layer_has_them():
    g = C.get(ARCH)
    assert g.layer_pattern() == [("attn" if i == 5 else "ssm", "moe")
                                 for i in range(10)]
    j = C.get("jamba-v0.1-52b")
    assert j.layer_pattern() == [
        ("ssm", "mlp"), ("ssm", "moe"), ("ssm", "mlp"), ("ssm", "moe"),
        ("attn", "mlp"), ("ssm", "moe"), ("ssm", "mlp"), ("ssm", "moe")]


def test_parameter_counts_include_shared_and_held_experts():
    g = C.get(ARCH)
    assert round(g.n_params() / 1e9, 2) == 4.42
    full = dataclasses.replace(g, n_layers=40, experts_held=0)
    assert round(full.n_params() / 1e9, 1) == 32.2
    # active: the shared expert and 10 of 72 experts (8.8 B: "A9B")
    assert round(full.n_active_params() / 1e9, 1) == 8.8


def test_engine_spans_carry_their_stats(tmp_path):
    arch = smoke()
    engine = Engine(arch, seed=0)
    prompts = np.random.default_rng(0).integers(2, arch.vocab, (2, 16))
    engine.generate(prompts.astype(np.int32), ServeConfig(max_new_tokens=4,
                                                          eos_id=-1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.generate(prompts.astype(np.int32),
                        ServeConfig(max_new_tokens=4, eos_id=-1))
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    got = {ev.name: dict(ev.stats)
           for plane in ProfileData.from_file(path).planes
           for line in plane.lines for ev in line.events
           if ev.name.startswith("engine.")}
    pf, dec = got["engine.prefill"], got["engine.decode"]
    assert pf["batch"] == dec["batch"] == 2 and pf["prompt"] == 16
    assert pf["steps"] == 0 and dec["steps"] == 3
    assert pf["routed_slots"] == 2 * 16 * arch.n_layers * arch.top_k
    assert dec["routed_slots"] == 3 * 2 * arch.n_layers * arch.top_k
    assert pf["dropped"] == dec["dropped"] == 0
    H, P, N = arch.ssm_heads, arch.ssm_head_dim, arch.ssm_state
    n_ssm = 4
    conv = (arch.ssm_conv - 1) * (arch.d_inner + 2 * N)
    assert pf["state_bytes"] == 4 * 2 * n_ssm * (H * P * N + conv)
    assert pf["kv_bytes"] == 4 * 2 * 2 * 2 * 20 * arch.n_kv_heads * arch.hd
    # the running totals count both calls
    assert engine.slots["routed_slots"] == 2 * (pf["routed_slots"]
                                                + dec["routed_slots"])
