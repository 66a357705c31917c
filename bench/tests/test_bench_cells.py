"""``BENCHMARK.json`` as the harness reads it: every name resolves to its
file, each cell gets the metrics it is judged on, and the serving mixes stay
tied to one measured knee."""
import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_support as S  # noqa: E402

from harness import common  # noqa: E402
import run as bench_run  # noqa: E402

BENCHMARK = common.load_json(S.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
CHAT, SATURATE = "serve.minicpm2b.chat", "serve.minicpm2b.saturate"
# the share of the knee each serving mix offers
KNEE_SHARE = {"chat": 0.8, "saturate": 1.5}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    wl, config, traffic = bench_run.cell(BENCHMARK, name)
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[wl["config"]]
    assert (S.ROOT / entry["file"]).is_file()
    assert (S.BENCH / "harness" / f"{config['runner']}.py").is_file()
    assert traffic, name
    for traced in (False, True):
        metrics = bench_run.metrics_for(BENCHMARK, name, traced)
        assert metrics, (name, traced)
        for m in metrics:
            assert (S.BENCH / "metrics" / f"{m['name']}.py").is_file(), m


def test_serving_cells_are_admitted_on_one_chip():
    wl = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert wl[CHAT]["chips"] == wl[SATURATE]["chips"] == 1
    assert {wl[CHAT]["config"], wl[SATURATE]["config"]} == {"minicpm-2b"}
    assert not (S.BENCH / "pending_cells.json").exists()


def test_every_bound_is_a_number_within_the_contract():
    for m in BENCHMARK["end_to_end"]:
        assert isinstance(m["bound"], float), m
        assert 0.01 <= m["bound"] <= 0.25, m


@pytest.mark.parametrize("name,want", [
    (CHAT, {"req_p50_ms", "tokens_per_s", "setup_s"}),
    (SATURATE, {"tokens_per_s", "setup_s"}),
])
def test_serving_cells_get_their_end_to_end_metrics(name, want):
    got = {m["name"] for m in bench_run.metrics_for(BENCHMARK, name, False)}
    assert got == want


@pytest.mark.parametrize("name", [CHAT, SATURATE])
def test_serving_per_layer_metrics_move_what_the_cell_reports(name):
    e2e = {m["name"] for m in bench_run.metrics_for(BENCHMARK, name, False)}
    per_layer = bench_run.metrics_for(BENCHMARK, name, True)
    assert per_layer
    assert all(m["moves"] in e2e for m in per_layer), per_layer


def knee_range(rate: float, share: float) -> tuple[float, float]:
    """The knees whose ``share`` rounds to ``rate`` at two significant
    digits."""
    half = 0.5 * 10 ** (math.floor(math.log10(rate)) - 1)
    return (rate - half) / share, (rate + half) / share


def test_serving_rates_come_from_one_knee():
    rates = {}
    for traffic, share in KNEE_SHARE.items():
        rate = common.load_json(S.BENCH / "traffic" / f"{traffic}.json")[
            "rate_per_s"]
        assert isinstance(rate, (int, float)) and rate > 0, (traffic, rate)
        assert float(f"{rate:.2g}") == rate, (traffic, rate)
        rates[traffic] = knee_range(rate, share)
    lo = max(r[0] for r in rates.values())
    hi = min(r[1] for r in rates.values())
    assert lo <= hi, rates


@pytest.mark.parametrize("rates,tied", [((1.2, 2.2), True),
                                        ((1.2, 2.3), True),
                                        ((1.2, 2.6), False),
                                        ((0.8, 2.2), False)])
def test_knee_range_ties_two_rates(rates, tied):
    chat, sat = knee_range(rates[0], 0.8), knee_range(rates[1], 1.5)
    assert (max(chat[0], sat[0]) <= min(chat[1], sat[1])) is tied
