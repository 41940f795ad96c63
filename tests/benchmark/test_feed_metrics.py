"""The input feed's layer metrics and the one clock, on the CPU: each
``benchmark/layers/feed_*.py`` reader on a recorded ``run`` dict, the
manifest's entries for them, the traced rehearsal of ``alexnet_live``
through the native loader, and the anchor and gap-attribution functions
on the recorded chip trace.  A file of its own beside
``test_benchmark.py``, whose helpers it borrows: a PR that changes the
program adds files to the benchmark and edits none."""

import os

import pytest

from benchmark import run, trace_reduce  # no jax at import
from tests.benchmark.test_benchmark import (  # noqa: F401  (clock: a fixture)
    _manifest, _recorded, _recorded_steps, _tiny_cell, clock,
)


# --------------------------------------------- the input feed's layer metrics

_FEED_PHASES = {
    "feed_source_ms": "feed.source",
    "feed_h2d_ms": "feed.h2d",
    "feed_backpressure_ms": "feed.backpressure",
    "feed_loader_blocked_ms": "feed.loader_blocked",
    "feed_produce_ms": "feed.produce",
}


@pytest.mark.parametrize("metric", sorted(_FEED_PHASES))
def test_feed_reader_gives_ms_a_step_and_none_without_its_phase(metric):
    """What ``timed_phases`` records for the live loop (fence off), as
    ``alexnet_live`` read on the chip: ten calls of the step."""
    phases = {
        "input_wait": 7.9, "device_put": 0.001, "compiled_step": 0.0085,
        "feed.source": 7.1, "feed.h2d": 1.2, "feed.backpressure": 0.2,
        "feed.loader_blocked": 5.9, "feed.produce": 17.0,
    }
    read = run.metric_reader(run.load_cell("alexnet_live")["layers_dir"], metric)
    probe = {"steps": 10, "wall_s": 8.5, "phases": phases}
    assert read({"dispatch": probe}) == pytest.approx(
        100.0 * phases[_FEED_PHASES[metric]]
    )
    # a program without the span (the parent commit), a feed that is not
    # the native loader, an untraced run: nothing to read, and no error
    without = {k: v for k, v in phases.items() if k != _FEED_PHASES[metric]}
    assert read({"dispatch": {**probe, "phases": without}}) is None
    assert read({"dispatch": {**probe, "steps": 0}}) is None
    assert read({}) is None


def test_feed_metrics_cells_are_the_manifests():
    m = {x["name"]: x for x in _manifest()["per_layer"]}
    for name in _FEED_PHASES:
        assert m[name]["layer"] == "Input feed" and m[name]["unit"] == "ms"
        assert m[name]["moves"] == "samples_per_s"
        assert m[name]["source"] == "program_span"
    assert m["feed_backpressure_ms"]["better"] == "higher"
    assert all(
        "workloads" not in m[n]
        for n in ("feed_source_ms", "feed_h2d_ms", "feed_backpressure_ms")
    )
    assert m["feed_loader_blocked_ms"]["workloads"] == ["alexnet_live"]
    assert m["feed_produce_ms"]["workloads"] == ["alexnet_live"]
    # retired with PR 28: since the loader lends its buffers it timed a
    # 4 KB copy of labels (the counter stays on the `input pipeline:` line)
    assert "feed_copy_out_ms" not in m
    assert not os.path.exists(os.path.join(
        run.load_cell("alexnet_live")["layers_dir"], "feed_copy_out_ms.py"))


def test_traced_rehearsal_lists_the_feed_metrics_and_they_add_up(
    clock, tmp_path, monkeypatch
):
    """``alexnet_live`` tiny, through the native loader and the staging
    thread: all five feed metrics are on the line, and the staging
    thread's three add up to the wall time of the part that read them,
    which are its counted steps alone.
    Every staging thread of the process reports to the current timeline:
    the feeds that other tests of this module keep open sit in
    ``feed.backpressure`` all the while, a wall's worth each."""
    from sparknet_tpu.telemetry import timeline

    recorded_runs = {}
    parked = len(timeline._in_flight)

    real = run.traced_parts

    def keep(recorded, built, trace_dir):
        recorded_runs["run"] = recorded
        return real(recorded, built, trace_dir)

    monkeypatch.setattr(run, "traced_steps", _recorded_steps)
    monkeypatch.setattr(run, "traced_parts", keep)
    cell = _tiny_cell("alexnet_live")
    cell["traffic"]["trace"].update(dispatch_steps=6)
    out = run.run_cell(
        cell, seed=11, seconds=0.5, trace=True, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 197e12},
    )
    assert out["correct"] is True, out
    assert set(_FEED_PHASES) <= set(out["metrics"])
    value = lambda name: out["metrics"][name]["value"]
    assert all(value(name) >= 0 for name in _FEED_PHASES)
    probe = recorded_runs["run"]["dispatch"]
    serial = (
        value("feed_source_ms") + value("feed_h2d_ms")
        + value("feed_backpressure_ms")
    )
    a_step = 1e3 * probe["wall_s"] / probe["steps"]
    assert serial == pytest.approx((1 + parked) * a_step, rel=0.1)
    assert value("feed_source_ms") + value("feed_h2d_ms") <= 1.01 * a_step
    # the loader's wait is a part of a next() of it
    assert value("feed_loader_blocked_ms") <= value("feed_source_ms") * 1.01
    # the ledger's idle_gaps holds the loop's own phases: the feed.* rows
    # overlap input_wait (feed.produce is worker-seconds) and are no gaps
    gaps = {name for name, _s in out["breakdown"]["idle_gaps"]}
    assert "input_wait" in gaps
    assert not any(name.startswith("feed.") for name in gaps), gaps
    assert any(name.startswith("feed.") for name in probe["phases"])
    # each part counted its steps after the traffic file's uncounted ones
    lead = cell["traffic"]["trace"]["lead_steps"]
    assert lead == 3
    for part, count in (("dispatch", 6), ("fenced", 2)):
        log = recorded_runs["run"][part]
        assert log["primed"] == lead + 1
        assert log["steps"] == log["completed"] == count
        assert log["wall_s"] == log["window_s"]
        assert len(log["losses"]) == lead + count + 2


# ------------------------------------------- uncounted steps before a part

class _FakeSolver:
    """``Solver.step``'s side of a part: it reads its timeline at every
    call, brackets ``compiled_step`` with it, and returns the metrics."""

    def __init__(self):
        self.timeline = None
        self.under = []  # the timeline each call ran under

    def step(self, feed, n):
        self.under.append(self.timeline)
        with self.timeline.phase("compiled_step"):
            return {"loss": float(next(feed))}


@pytest.mark.parametrize("lead, count, fence", [
    (0, 12, False),   # mlm_s512_bs64: no key, the part opens on the loop's
    (0, 8, True),     # first completion as it always did
    (24, 48, False),  # live_bs1024
    (24, 32, True),
    (3, 1, False),
])
def test_counted_steps_of_a_part_follow_its_uncounted_ones(lead, count, fence):
    solver, before = _FakeSolver(), object()
    solver.timeline = before
    feed = iter(range(1000))
    part = run.timed_phases(solver, feed, "loss", count, fence=fence, lead=lead)
    assert solver.timeline is before
    # the step that opens the window and the one in flight then are the
    # last two uncounted ones; `count` are dispatched inside the window,
    # and none after it
    assert len(solver.under) == lead + 2 + count
    uncounted, counted = solver.under[0], solver.under[-1]
    assert uncounted is not counted and uncounted.fence == counted.fence == fence
    assert all(t is uncounted for t in solver.under[: lead + 2])
    assert all(t is counted for t in solver.under[lead + 2:])
    assert part["steps"] == part["completed"] == count
    assert part["primed"] == lead + 1 and part["attempted"] == count + 1
    assert part["losses"] == [float(i) for i in range(lead + count + 2)]
    assert next(feed) == lead + count + 2  # no batch taken and dropped
    assert part["wall_s"] == part["window_s"] == pytest.approx(
        sum(part["step_s"]))
    assert set(part["phases"]) == {"compiled_step"}


def test_a_traffic_file_without_lead_steps_runs_its_parts_as_before(
    clock, tmp_path, monkeypatch
):
    """``mlm_s512_bs64`` names no ``lead_steps``: its parts open on the
    loop's first completion, and the breakdown names the loop's phases."""
    parts = {}
    real = run.timed_phases

    def keep(solver, feed, loss_key, count, fence, lead=0):
        parts[fence] = (lead, real(solver, feed, loss_key, count, fence, lead))
        return parts[fence][1]

    monkeypatch.setattr(run, "timed_phases", keep)
    monkeypatch.setattr(run, "traced_steps", _recorded_steps)
    assert "lead_steps" not in run.load_cell("bert_mlm")["traffic"]["trace"]
    assert run.load_cell("alexnet_live")["traffic"]["trace"]["lead_steps"] == 24
    out = run.run_cell(
        _tiny_cell("bert_mlm"), seed=28, seconds=0.5, trace=True, clock=clock,
        trace_dir=str(tmp_path), peaks={"bf16_flops_per_s": 197e12},
    )
    assert out["correct"] is True, out
    for fence in (False, True):
        lead, part = parts[fence]
        assert lead == 0 and part["primed"] == 1
        assert part["steps"] == part["completed"] == 2
    gaps = [name for name, _s in out["breakdown"]["idle_gaps"]]
    assert "input_wait" in gaps and not any(n.startswith("feed.") for n in gaps)
    # every number `correct` compared is on the line beside its limit, last
    assert list(out)[-1] == "compared"
    assert out["compared"]["reference_abs_diff"]["value"] <= (
        out["compared"]["reference_abs_diff"]["at_most"])
    assert out["compared"]["iter_advance"]["value"] == (
        out["compared"]["iter_advance"]["equal_to"])


# --------------------------------------------------- one clock, and the gaps

def _chip_modules():
    """The three recorded executions, moved to a device clock that starts
    somewhere else than the wall clock, behind one run of the anchor."""
    steps = _recorded()["devices"]["/device:TPU:0"]["modules"]
    device_t0 = 5_000_000_000
    anchor = ("jit_sparknet_anchor(1)", device_t0 - 900_000_000, 9_000)
    return [anchor] + [(n, s + device_t0, d) for n, s, d in steps]


def test_anchor_fixes_the_offset_to_the_brackets_width():
    from sparknet_tpu.telemetry import trace

    modules = _chip_modules()
    wall_of_anchor = 1_790_000_000_000_000_000  # an epoch time, ns
    true_offset = wall_of_anchor - modules[0][1]
    before, after = wall_of_anchor - 120_000, wall_of_anchor + 9_000 + 60_000
    offset, width = trace.anchor_offset(
        modules, "jit_sparknet_anchor", before, after
    )
    assert width == 189_000
    assert abs(offset - true_offset) <= (width - 9_000) // 2 + 1
    # a plane already on the epoch clock: the same rule, an offset within
    # the bracket's width of none
    on_epoch = [(n, s + true_offset, d) for n, s, d in modules]
    small, _ = trace.anchor_offset(on_epoch, "jit_sparknet_anchor", before, after)
    assert abs(small) <= (width - 9_000) // 2 + 1
    # an event that cannot have run inside the bracket, or no anchor at all
    with pytest.raises(ValueError):
        trace.anchor_offset(modules, "jit_sparknet_anchor", before, before + 5_000)
    with pytest.raises(ValueError):
        trace.anchor_offset(modules[1:], "jit_sparknet_anchor", before, after)
    assert trace.step_program(modules, but="jit_sparknet_anchor").startswith(
        "jit_fused("
    )
    track = trace.device_track(modules[1:], offset, label="device")
    assert track[0]["ph"] == "M" and track[0]["args"]["name"] == "device"
    assert [e["ph"] for e in track[1:]] == ["X"] * 3
    assert track[1]["ts"] == pytest.approx((modules[1][1] + offset) / 1e3)
    assert track[1]["dur"] == pytest.approx(68367.889)
    assert len({(e["pid"], e["tid"]) for e in track}) == 1


def test_longest_gaps_name_the_phases_that_cover_them():
    from sparknet_tpu.telemetry import trace

    # the recorded steps run back to back (9 us apart); pull the third one
    # 800 ms away, as a step that waited for its batch
    (n, s0, d0), (_, s1, d1), (_, s2, d2) = _recorded()["devices"][
        "/device:TPU:0"
    ]["modules"]
    late = s2 + 800_000_000
    steps = [(n, s0, d0), (n, s1, d1), (n, late, d2)]
    end1 = s1 + d1
    loop = [
        ("compiled_step", s0, 1_000_000),
        ("input_wait", end1 - 50_000_000, 700_000_000),
        ("compiled_step", end1 + 650_000_000, 150_000_000),
    ]
    beside = [
        ("feed.source", end1 - 100_000_000, 720_000_000),
        ("feed.h2d", end1 + 620_000_000, 110_000_000),
        ("feed.backpressure", end1 + 730_000_000, 1_000),
    ]
    gaps = trace.longest_gaps(steps, loop, beside, n=5)
    assert [g["after"] for g in gaps] == [1, 0]  # longest first
    long, short = gaps
    assert long["gap_ns"] == late - end1
    assert long["loop"][0] == "input_wait"
    assert long["loop"][1] == pytest.approx(650e6 / long["gap_ns"])
    assert long["beside"][0] == "feed.source"
    assert long["beside"][1] == pytest.approx(620e6 / long["gap_ns"])
    assert short["gap_ns"] == s1 - (s0 + d0) and short["loop"] is None
    assert trace.longest_gaps(steps, loop, beside, n=1) == [long]
    table = trace.gap_table(gaps).splitlines()
    assert len(table) == 3 and "input_wait 81%" in table[1]
    assert "feed.source 77%" in table[1]
    assert table[2].split()[2:] == ["-", "feed.source", "100%"]
