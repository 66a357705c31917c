"""The hybrid (Granite 4.0-H) serving cell's harness on the CPU: its counts,
its readers, its reference's weights and its runner's checks."""
import dataclasses
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_support as S  # noqa: E402

from harness import (  # noqa: E402
    common,
    engine_spans,
    hybrid_counts,
    hybrid_lm,
    hybrid_serving,
)

NAME = "granite-4.0-h-small"
CELL = "serve.granite4hsmall.saturate"
# the program's SMOKE variant under the published keys
SMALL = {"num_hidden_layers": 6, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "intermediate_size": 32, "shared_intermediate_size": 48,
         "vocab_size": 256, "vocab": 256, "router_experts": 8,
         "num_local_experts": 4, "expert_first": 2,
         "num_experts_per_tok": 3, "mamba_d_state": 16, "mamba_expand": 2,
         "mamba_d_head": 16, "mamba_n_heads": 8, "mamba_d_conv": 4,
         "mamba_chunk_size": 8, "attention_multiplier": 0.0625,
         "layer_types": ["mamba", "attention", "mamba"] * 2, "max_batch": 2}
SMALL_TRAFFIC = {"prompt_len": {"16": 0.75, "24": 0.25},
                 "max_new": {"4": 0.75, "8": 0.25}, "rate_per_s": 4.0}


def small_config() -> dict:
    return dict(common.load_json(S.BENCH / "configs" / f"{NAME}.json"),
                **SMALL)


def small_arch():
    from repro import configs

    return dataclasses.replace(configs.get(NAME, smoke=True),
                               param_dtype="bfloat16")


def published(cfg, layers: int, held: int) -> dict:
    types = common.load_json(
        S.BENCH / "configs" / f"{NAME}.json")["layer_types"]
    period = types[:10]
    return dict(cfg, num_hidden_layers=layers, num_local_experts=held,
                layer_types=period * (layers // 10))


def test_counts_reproduce_the_share_and_the_model():
    cfg = common.load_json(S.BENCH / "configs" / f"{NAME}.json")
    share = hybrid_counts.params(cfg)["total"]
    assert round(share / 1e9, 2) == 4.42
    assert round(share * hybrid_counts.PARAM_BYTES / 1e9, 2) == 8.84
    full = hybrid_counts.params(published(cfg, 40, 72))["total"]
    assert round(full / 1e9, 1) == 32.2
    # the program counts the same parameters, save the conv and norm
    # vectors it leaves out
    from repro import configs

    arch = configs.get(NAME)
    assert abs(arch.n_params() - share) / share < 1e-3


def test_decode_bytes_by_hand():
    cfg = common.load_json(S.BENCH / "configs" / f"{NAME}.json")
    assert hybrid_counts.expected_held_experts(cfg, 1) == pytest.approx(1.25)
    assert hybrid_counts.expected_held_experts(cfg, 4) == pytest.approx(
        9 * (1 - (62 / 72) ** 4))
    # 18 Mamba layers of (128 x 64 x 128) float32 state and a bfloat16
    # window of 3 x (8192 + 256)
    assert hybrid_counts.state_bytes(cfg) == 18 * (128 * 64 * 128 * 4
                                                   + 3 * 8448 * 2)
    assert hybrid_counts.kv_bytes(cfg, 576) == 2 * 2 * 576 * 8 * 128 * 2
    b4 = hybrid_counts.decode_bytes(cfg, 4, 576)
    assert 7.5e9 < b4 < 7.7e9
    assert hybrid_counts.decode_bytes(cfg, 1, 576) < b4


def test_weights_match_the_programs_parameter_tree():
    from repro.models.model import build

    cfg = small_config()
    params = hybrid_lm.make_params(cfg, 2**31 + 5)
    want = build(small_arch()).abstract()
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)


def test_runner_refuses_a_configuration_the_program_does_not_run():
    arch = small_arch()
    hybrid_serving.check_model(small_config(), arch)
    for key, value in (("num_local_experts", 8), ("mamba_chunk_size", 16),
                       ("attention_multiplier", 0.25),
                       ("layer_types", ["mamba"] * 6)):
        with pytest.raises(ValueError, match=key):
            hybrid_serving.check_model(dict(small_config(), **{key: value}),
                                       arch)
    # the file at its full size against the program's own configuration
    from repro import configs

    hybrid_serving.check_model(
        common.load_json(S.BENCH / "configs" / f"{NAME}.json"),
        configs.get(NAME))


# two ticks; the engine's spans at batch 4 in the first, at batch 1 in the
# second
SPANS = [("tick", 0, 100, {}),
         ("engine.prefill", 1, 20, {"batch": 4, "prompt": 512, "steps": 0,
                                    "held_slots": 90, "routed_slots": 720,
                                    "dropped": 0}),
         ("engine.decode", 20, 99, {"batch": 4, "prompt": 512, "steps": 10,
                                    "held_slots": 900, "routed_slots": 8000,
                                    "dropped": 0}),
         ("tick", 150, 200, {}),
         ("engine.decode", 161, 199, {"batch": 1, "prompt": 1024,
                                      "steps": 30, "held_slots": 540,
                                      "routed_slots": 6000, "dropped": 0})]
MODULES = [("jit_prefill(1)", 2, 12), ("jit_prefill(2)", 150, 160),
           ("jit_decode(3)", 21, 25), ("jit_decode(3)", 25, 31)]


def traced_record():
    cfg = common.load_json(S.BENCH / "configs" / f"{NAME}.json")
    tr = common.load_json(S.BENCH / "traffic" / "saturate_long.json")
    bench = [(n, s, e) for n, s, e, _ in SPANS if n == "tick"]
    return {"trace": {"ops": [], "modules": MODULES, "spans": bench,
                      "lo": 0, "hi": 200},
            "requests": [], "config": cfg, "traffic": tr,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_synthetic_record(monkeypatch):
    monkeypatch.setattr(engine_spans, "load", lambda d: tuple(SPANS))
    rec = traced_record()
    # 900 + 540 held slots over 10 + 30 steps, 9 held experts and 20 layers
    assert engine_spans.held_expert_load(rec) == pytest.approx(0.2)
    assert engine_spans.prefill_s(rec) == pytest.approx(10e-9)
    # each span's steps at its own batch and prompt length, over 5 ns a step
    cfg = rec["config"]
    least = (10 * hybrid_counts.decode_bytes(cfg, 4, 512)
             + 30 * hybrid_counts.decode_bytes(cfg, 1, 1024)) / 40 / 819e9
    assert engine_spans.decode_roofline(rec) == pytest.approx(
        100 * least / 5e-9)


def test_readers_find_nothing_to_read(monkeypatch):
    monkeypatch.setattr(engine_spans, "load", lambda d: tuple(SPANS))
    untraced = dict(traced_record(), trace=None)
    for read in (engine_spans.held_expert_load, engine_spans.prefill_s,
                 engine_spans.decode_roofline):
        assert read(untraced) is None
    # another run's trace: its ticks differ
    other = traced_record()
    other["trace"]["spans"] = [("tick", 5, 100)]
    assert engine_spans.held_expert_load(other) is None
    # a program without engine spans
    monkeypatch.setattr(engine_spans, "load", lambda d: tuple(
        s for s in SPANS if s[0] == "tick"))
    assert engine_spans.held_expert_load(traced_record()) is None


def hybrid_run(seed=2**31 + 17, fault=None):
    cfg = small_config()
    tr = dict(common.load_json(S.BENCH / "traffic" / "saturate_long.json"),
              **SMALL_TRAFFIC)
    prog = hybrid_serving.serving.program()
    prog.arch = lambda name: small_arch()
    if fault is not None:
        prog.Engine = fault(prog.Engine)
    return hybrid_serving.run(S.context(cfg, tr, seed=seed, seconds=2.0,
                                        program=prog))


def test_sound_run_is_correct():
    out = hybrid_run()
    assert out["correct"], out["checks"]
    checks = {name: value for name, value, _ in out["checks"]}
    assert checks["dropped_slots"] == 0
    assert 0 < checks["logit_err"] < float("inf")
    slots = out["rec"]["expert_slots"]
    assert 0 < slots["held_slots"] < slots["routed_slots"]


def capacity_drops(Engine):
    """Prefill routed with the training capacity, which drops slots."""
    class Capped(Engine):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            from repro.models import transformer

            def prefill(params, batch):
                hidden, _, cache = transformer.forward_full(
                    params, self.cfg, tokens=batch["tokens"],
                    want_cache=True, dropless=False)
                return cache, transformer.unembed(params, self.cfg,
                                                  hidden[:, -1:])
            self._prefill = jax.jit(prefill)
    return Capped


def test_dropped_slots_are_not_correct():
    out = hybrid_run(fault=capacity_drops)
    checks = {name: value for name, value, _ in out["checks"]}
    assert checks["dropped_slots"] > 0
    assert not out["correct"]


def test_fp8_control_reads_wider_than_the_program():
    """The reference in per-tensor float8, in the program's place, puts
    tokens first whose gap is far wider than the program's."""
    cfg = small_config()
    prog = hybrid_serving.serving.program()
    params = hybrid_lm.make_params(cfg, 5)
    engine = prog.Engine(small_arch(), params=params)
    rng = np.random.default_rng(1)
    reqs = []
    for plen, new in ((16, 16), (24, 24)):
        prompt = rng.integers(2, cfg["vocab"], plen).astype(np.int32)
        toks = np.asarray(engine.generate(
            prompt[None], prog.ServeConfig(max_new_tokens=new)))[0]
        reqs.append({"prompt": prompt, "max_new": new, "tokens": toks})
    program_gap = hybrid_serving.compare(cfg, params, reqs)
    control_gap = hybrid_serving.compare(cfg, params, reqs,
                                         quant=hybrid_lm.fp8_round)
    assert control_gap > 2 * program_gap, (control_gap, program_gap)
    # the logits themselves: the engine's against the float8 reference's
    program_err = hybrid_serving.logit_err(
        cfg, params, reqs, hybrid_serving.engine_logits(engine))
    low = jax.jit(lambda p, t, q: hybrid_lm.forward_logits(
        cfg, p, t, q, hybrid_lm.fp8_round))
    control_err = hybrid_serving.logit_err(
        cfg, params, reqs, lambda r, t, q: low(params, t, q))
    assert control_err > 2 * program_err, (control_err, program_err)


def test_relative_error_by_hand():
    ref = np.array([[3.0, -4.0, 0.0, 0.0], [1.0, 1.0, 1.0, 1.0]], np.float32)
    got = ref + np.array([[0.5, 0, 0, 0], [0, 0, -0.25, 0]], np.float32)
    # rms 2.5 and 1: 0.5 / 2.5 and 0.25 / 1
    np.testing.assert_allclose(
        np.asarray(hybrid_serving.relative_error(ref, got)), [0.2, 0.25])
