"""Driver-facing entry points must never regress.

These tests import and execute the exact artifacts the driver runs —
``__graft_entry__.entry`` and ``__graft_entry__.dryrun_multichip`` — so
any regression fails CI before it can cost a round.  (The benchmark,
``benchmark/run.py``, is rehearsed under ``tests/benchmark``.)
"""

import os
import sys

import numpy as np
import pytest
import jax

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def test_console_entry_points_resolve():
    """Every [project.scripts] target in pyproject.toml must import and
    expose its callable — ``serve`` and friends ship as console
    commands, and a typo'd target only fails at install time otherwise."""
    import importlib
    import re

    with open(os.path.join(_ROOT, "pyproject.toml")) as fh:
        text = fh.read()
    section = re.search(
        r"\[project\.scripts\]\n(.*?)(\n\[|\Z)", text, re.S
    ).group(1)
    targets = dict(re.findall(r'([\w-]+)\s*=\s*"([\w.:]+)"', section))
    assert "serve" in targets and "cifar-app" in targets
    for name, target in targets.items():
        mod_name, _, attr = target.partition(":")
        mod = importlib.import_module(mod_name)
        assert callable(getattr(mod, attr)), f"{name} -> {target}"


def test_entry_compiles_and_runs():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    loss = jax.jit(fn)(*args)
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_dryrun_multichip_all_axes():
    # conftest already forced the 8-device CPU mesh; _ensure_devices
    # must find it and say so
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_dryrun_ensure_devices_is_idempotent(capsys):
    import __graft_entry__ as ge

    ge._ensure_devices(8)
    assert len(jax.devices()) >= 8
    # the dry run names the platform it runs on
    assert "platform=cpu" in capsys.readouterr().out


def test_dryrun_native_with_too_few_devices_is_an_error(monkeypatch):
    """GRAFT_DRYRUN_NATIVE=1 means the accelerator as it is: asking for
    more devices than it has fails instead of becoming a CPU run."""
    import __graft_entry__ as ge

    monkeypatch.setenv("GRAFT_DRYRUN_NATIVE", "1")
    with pytest.raises(RuntimeError, match="needs 64 devices"):
        ge._ensure_devices(64)


def test_no_document_names_what_is_gone():
    """The documents a new builder reads first describe the system as it
    is: one yardstick (``benchmark/run.py``) and one step program.  The
    histories (CHANGES.md, PERF.md, ROADMAP.md) are not read here."""
    import glob

    gone = ("bench.py", "BENCH_MODEL", "bench_diff", "scan_steps", "--scan",
            "SPARKNET_FUSED_STEP")
    documents = [
        os.path.join(_ROOT, "README.md"),
        os.path.join(_ROOT, ".claude", "skills", "verify", "SKILL.md"),
        *sorted(glob.glob(os.path.join(_ROOT, "docs", "*.md"))),
    ]
    assert len(documents) > 5
    named = []
    for path in documents:
        with open(path) as fh:
            for number, line in enumerate(fh, 1):
                named += [
                    f"{os.path.relpath(path, _ROOT)}:{number}: {word}"
                    for word in gone if word in line
                ]
    assert not named, "\n".join(named)
