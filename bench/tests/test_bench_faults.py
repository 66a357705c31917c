"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run of a cell's runner on the CPU at a small size,
past the harness's look for a chip, with one fault planted in the program
the window calls, and checks that ``correct`` reads false -- and, as the
control of each pair, that the same run without the fault reads true."""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_support as S  # noqa: E402

from harness import common, dense_lm, planner, serving  # noqa: E402


def planner_run(mode, fault=None, seed=2**31 + 3):
    cfg = S.small_config("rgg16k-p64")
    tr = common.load_json(S.BENCH / "traffic" / f"{mode}.json")
    prog = planner.program()
    if fault is not None:
        prog.plan = fault(prog.plan)
    return planner.run(S.context(cfg, tr, seed=seed, seconds=1.0,
                                 program=prog))


def altered_answer(plan):
    """One CEFT value altered where the sweep produces it."""
    def wrapped(g, comp, m):
        res = plan(g, comp, m)
        table = res.ceft.copy()
        table[g.n // 2, 0] *= 1.01
        return dataclasses.replace(res, ceft=table)
    return wrapped


def unchanged_state(plan):
    """Every call returns the first call's plan: the state never moves."""
    first = []

    def wrapped(g, comp, m):
        if not first:
            first.append(plan(g, comp, m))
        return first[0]
    return wrapped


@pytest.mark.parametrize("mode", ["replan", "fresh"])
def test_planner_sound_run_is_correct(mode):
    assert planner_run(mode)["correct"]


@pytest.mark.parametrize("fault", [altered_answer, unchanged_state])
@pytest.mark.parametrize("mode", ["replan", "fresh"])
def test_planner_fault_is_not_correct(mode, fault):
    out = planner_run(mode, fault)
    assert not out["correct"], out["checks"]


def test_planner_bf16_control_is_not_correct():
    """The float64 reference computed in bfloat16, put in the program's
    place, fails the comparison."""
    import ml_dtypes

    from harness import ceft_ref
    from repro.core.ceft import CeftResult

    def control(plan):
        def wrapped(g, comp, m):
            src = np.repeat(np.arange(g.n), np.diff(g.cindptr))
            r = ceft_ref.ceft(g.n, src, g.cindices, g.cdata, comp, m.L, m.bw,
                              dtype=ml_dtypes.bfloat16)
            sink, proc = r["path"][-1]
            return CeftResult(r["ceft"], r["pred_task"], r["pred_proc"],
                              sink, proc, r["cpl"])
        return wrapped
    out = planner_run("replan", control)
    assert not out["correct"], out["checks"]


def serving_run(fault_engine=None, fault_router=None, seed=2**31 + 11):
    cfg = S.small_config("minicpm-2b")
    tr = dict(common.load_json(S.BENCH / "traffic" / "chat.json"),
              rate_per_s=4.0)
    prog = serving.program()
    prog.arch = lambda name: S.small_arch(cfg)
    if fault_engine is not None:
        prog.Engine = fault_engine(prog.Engine)
    if fault_router is not None:
        prog.Router = fault_router(prog.Router)
    return serving.run(S.context(cfg, tr, seed=seed, seconds=3.0,
                                 program=prog))


def altered_token(Engine):
    """Every generated token replaced where the engine produces it."""
    class Altered(Engine):
        def generate(self, prompts, scfg=None):
            toks = np.array(super().generate(prompts, scfg))
            P = prompts.shape[1]
            toks[:, P:] = (toks[:, P:] + 7919) % self.cfg.vocab
            return toks
    return Altered


def unchanged_cache(Engine):
    """Every decode step hands back the cache it was given: the step's
    state never moves past the prompt."""
    class Stale(Engine):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            decode = self._decode

            def stale(params, cache, tokens, pos):
                logits, _ = decode(params, cache, tokens, pos)
                return logits, cache
            self._decode = stale
    return Stale


def half_left_out(Router):
    """Every second request of the workload is dropped from its batch."""
    class Halved(Router):
        seen = 0

        def run_dispatch(self, d):
            out = super().run_dispatch(d)
            keep = {}
            for rid, toks in out.items():
                Halved.seen += 1
                if Halved.seen % 2:
                    keep[rid] = toks
            return keep
    return Halved


def test_serving_sound_run_is_correct():
    out = serving_run()
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["token", "cache", "half"])
def test_serving_fault_is_not_correct(fault):
    if fault == "half":
        out = serving_run(fault_router=half_left_out)
    else:
        out = serving_run(fault_engine={"token": altered_token,
                                        "cache": unchanged_cache}[fault])
    assert not out["correct"], out["checks"]


def test_serving_fp8_control_reads_wider_than_the_program():
    """The reference in per-tensor float8, in the program's place, puts
    tokens first whose gap is far wider than the program's."""
    cfg = S.small_config("minicpm-2b")
    prog = serving.program()
    arch = S.small_arch(cfg)
    params = dense_lm.make_params(cfg, 5)
    engine = prog.Engine(arch, params=params, profile=cfg["profile"])
    rng = np.random.default_rng(1)
    reqs = []
    for plen, new in ((128, 16), (192, 64)):
        prompt = rng.integers(2, cfg["vocab"], plen).astype(np.int32)
        toks = np.asarray(engine.generate(
            prompt[None], prog.ServeConfig(max_new_tokens=new)))[0]
        reqs.append({"prompt": prompt, "max_new": new, "tokens": toks})
    program_gap = serving.compare(cfg, params, reqs)
    control_gap = serving.compare(cfg, params, reqs,
                                  quant=dense_lm.fp8_round)
    assert control_gap > 3 * program_gap, (control_gap, program_gap)
