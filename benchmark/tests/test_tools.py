"""The tools that found the cells' rates: the knee's one rule on
hand-made windows, the driver's spread, and a sweep at a tiny size."""
import json
import os

import pytest

from benchmark.harness import manifest, stats
from benchmark.tools import runset, seedset, sweep


def _row(value, sent, finished, ttft_p90, failed=0):
    return {"value": value, "sent": sent, "finished": finished,
            "failed": failed, "ttft_p90_ms": ttft_p90}


@pytest.mark.parametrize("rows,knee", [
    # flat up to 3/s, the first-token tail triples at 4/s
    ([_row(1, 40, 39, 300), _row(2, 80, 77, 380), _row(3, 120, 112, 520),
      _row(4, 160, 150, 1600), _row(5, 200, 150, 5000)], 3),
    # the tail grows slowly, the finished fall behind at 3/s
    ([_row(1, 40, 38, 300), _row(2, 80, 74, 400), _row(3, 120, 100, 700)], 2),
    # a refused request is above the knee whatever the tails
    ([_row(1, 40, 38, 300), _row(2, 80, 78, 310, failed=1)], 1),
    # a window that gave no row (late generator) fails; given in any order
    ([{"value": 2}, _row(1, 40, 38, 300)], 1),
    # a rate that passes above one that failed does not count
    ([_row(1, 40, 38, 300), _row(2, 80, 60, 400), _row(3, 120, 115, 500)], 1),
    ([_row(1, 40, 30, 300)], None)])
def test_the_knee_by_the_one_rule(rows, knee):
    assert sweep.knee(rows) == knee


def test_the_drivers_spread_leaves_out_the_farthest_run():
    vals = [10.0, 10.2, 10.1, 9.9, 10.3, 12.0]
    assert runset.trimmed_range(vals) == pytest.approx(0.4 / 10.15)
    red = runset.reduce_set(
        [{"metrics": {"m": {"value": v, "unit": "ms"}}} for v in vals])
    assert red["m"]["trimmed_range"] < red["m"]["spread"] * 2
    assert red["m"]["median"] == pytest.approx(10.15)


def test_sweep_at_a_tiny_size(tiny_root, cpu_device, capsys, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", tiny_root)
    assert sweep.main(["--workload", "tiny-chat", "--values", "4,8",
                       "--seconds", "2", "--seed", str(2**31 + 5)]) == 0
    out = capsys.readouterr().out
    rows = [json.loads(ln[len("sweep: "):]) for ln in out.splitlines()
            if ln.startswith("sweep: {")]
    assert [r["value"] for r in rows] == [4.0, 8.0]
    assert all(r["sent"] >= r["finished"] and r["ttft_p90_ms"] > 0
               and r["rows_live"] >= 1 for r in rows)
    assert "sweep: knee by the rule" in out


def test_seedset_keeps_every_window_whole(tiny_root, cpu_device, capsys,
                                         monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", tiny_root)
    monkeypatch.setattr(seedset, "ROOT", tiny_root)
    plan = [{"sweep": [4, 8], "seeds": [2**31 + 5], "tag": "s"},
            {"windows": [[None, 7], [None, 7], [6.0, 8]], "tag": "w",
             "mix": {"order_block": 4}}]
    assert seedset.main(["--workload", "tiny-chat", "--plan",
                         json.dumps(plan), "--seconds", "2",
                         "--sweep-seconds", "2", "--out", "o"]) == 0
    out = capsys.readouterr().out
    assert "seedset: knee kept" in out and "seedset: w: tpot_p90_ms" in out
    kept = sorted(os.listdir(os.path.join(tiny_root, "chiprun_out", "o")))
    assert len(kept) == 5 and kept[0].startswith("s_sweep_s")
    with open(os.path.join(tiny_root, "chiprun_out", "o", kept[-1])) as f:
        row = json.load(f)
    assert row["mix"] == {"order_block": 4} and row["seed"] == 8
    assert len(row["requests"]) == row["sent"] == 12
    assert row["blocks_ms"] and row["ticks"]
    assert stats.percentile(row["blocks_ms"], 90) == pytest.approx(
        row["tpot_p90_ms"], abs=1e-3)


def test_runset_keeps_every_run_and_reduces_the_set(tmp_path, monkeypatch,
                                                    capsys):
    """One child a seed, as the driver runs them; here a child that prints
    a result line without touching JAX, and one that dies."""
    import types
    values = iter([9.1, 9.3, None, 9.2, 12.0])

    def child(cmd, **_kw):
        v = next(values)
        if v is None:
            return types.SimpleNamespace(returncode=1, stdout="",
                                         stderr="LateGenerator: late\n")
        line = {"correct": True, "attempted": 10, "failed": 0,
                "metrics": {"tpot_p90_ms": {"value": v, "unit": "ms"}},
                "device": {"memory_peak_bytes": 1}}
        return types.SimpleNamespace(
            returncode=0, stderr="", stdout="window: 10 sent\ngenerator "
            "lateness p90 1 ms\n" + json.dumps(line) + "\n")

    monkeypatch.setattr(runset.subprocess, "run", child)
    monkeypatch.setattr(runset, "ROOT", str(tmp_path))
    assert runset.main(["--workload", "w", "--seeds", "1,2,3,4,5",
                        "--seconds", "1", "--out", "o"]) == 1
    out = tmp_path / "chiprun_out" / "o"
    assert (out / "set0_seed3.txt").read_text().startswith("exit 1\n")
    assert "LateGenerator" in (out / "set0_seed3.txt").read_text()
    summary = (out / "summary.txt").read_text()
    assert summary == capsys.readouterr().out
    assert "seed 3: exit 1" in summary and "generator lateness" in summary
    reduced = json.loads(summary.splitlines()[-1].split("reduced: ")[1])
    assert reduced["tpot_p90_ms"]["values"] == [9.1, 9.3, 9.2, 12.0]
    assert reduced["tpot_p90_ms"]["trimmed_range"] == pytest.approx(0.2 / 9.25)
    # the quartiles of 9.1, 9.2, 9.3 (12.0 left out) are the ends
    assert reduced["tpot_p90_ms"]["driver_spread"] == pytest.approx(0.2 / 9.25)
