"""Guards from the v5e bring-up (PR 21): the compile cache is placed by
one rule, a parent that starts chip-holding children stays off JAX and
hands out one chip each, and nothing hides which device or feed ran."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _python(code: str, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    for name, value in env.items():
        if value is None:
            full.pop(name, None)
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=300, cwd=_ROOT, env=full,
    )


# ------------------------------------------------------------- compile cache

# every entry point applies the rule first thing in main(); ``--help``
# makes argparse leave right after it
_ENTRY_POINTS = """
import importlib, jax
for name in ("sparknet_tpu.apps.imagenet_app", "sparknet_tpu.apps.cifar_app",
             "sparknet_tpu.apps.bert_app", "sparknet_tpu.tools.caffe",
             "sparknet_tpu.tools.serve", "sparknet_tpu.serve.replica",
             "sparknet_tpu.deploy.trainer"):
    if RESET:  # so that each entry point has to set it again
        jax.config.update("jax_compilation_cache_dir", None)
    main = importlib.import_module(name).main
    try:
        main(["--help"])
    except SystemExit:
        pass
    print("RESULT", name, jax.config.jax_compilation_cache_dir)
from sparknet_tpu.serve.compile_cache import enable_persistent_cache
info = enable_persistent_cache(ROOT, "f00d")
print("RESULT serve", jax.config.jax_compilation_cache_dir, info["dir"])
"""


def _results(out):
    # (--help text shares stdout with the lines the probe prints)
    return [
        l.split()[1:] for l in out.stdout.splitlines()
        if l.startswith("RESULT ")
    ]


def test_env_variable_places_the_cache_everywhere(tmp_path):
    placed = str(tmp_path / "placed")
    out = _python(
        f"ROOT, RESET = {str(tmp_path / 'root')!r}, False\n" + _ENTRY_POINTS,
        JAX_COMPILATION_CACHE_DIR=placed,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = _results(out)
    # no entry point moves jax's directory off the variable's, and the
    # serving cache skips its per-net subdirectory as well
    assert [l[1] for l in lines[:-1]] == [placed] * 7, lines
    assert lines[-1][1:] == [placed, placed]


def test_unset_resolves_to_the_checkout_cache(tmp_path):
    root = str(tmp_path / "root")
    out = _python(
        f"ROOT, RESET = {root!r}, True\n" + _ENTRY_POINTS,
        JAX_COMPILATION_CACHE_DIR=None,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = _results(out)
    default = os.path.join(_ROOT, ".jax_cache")
    assert [l[1] for l in lines[:-1]] == [default] * 7, lines
    # an operator's --compile-cache root keeps its per-net subdirectory
    assert lines[-1][1:] == [os.path.join(root, "f00d")] * 2


def test_one_place_sets_the_cache_directory():
    hits = []
    paths = [os.path.join(_ROOT, "chip_smoke.py")]
    for base, _dirs, files in os.walk(os.path.join(_ROOT, "sparknet_tpu")):
        paths += [os.path.join(base, f) for f in files if f.endswith(".py")]
    for path in paths:
        with open(path) as fh:
            if "jax_compilation_cache_dir" in fh.read():
                hits.append(os.path.relpath(path, _ROOT))
    assert hits == ["sparknet_tpu/utils/compile_cache.py"]


# ------------------------------------------------------ one process per chip

def test_parents_of_chip_holding_children_stay_off_jax():
    """The supervisor parent and the serving router import the package,
    place the compile cache and parse prototxt text — none of which may
    create a backend (it would hold the chip their children need)."""
    out = _python("""
        from sparknet_tpu.utils import compile_cache
        compile_cache.enable()
        import sparknet_tpu.apps.cifar_app as app
        from sparknet_tpu.proto import caffe_pb
        from sparknet_tpu.supervise import supervisor
        args = app.arg_parser().parse_args(["--supervise"])
        caffe_pb.load_solver(args.solver)
        import sparknet_tpu.tools.serve
        from sparknet_tpu.serve.replica import add_engine_args
        from sparknet_tpu.serve.router import Router
        from sparknet_tpu.supervise.pool import ChildPool
        from sparknet_tpu.autoscale.controller import AutoscaleController
        from sparknet_tpu.parallel.partition import backend_initialized
        print(backend_initialized())
    """)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "False"


def test_chip_count_and_one_chip_env(monkeypatch):
    from sparknet_tpu.utils import chips

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert chips.local_chip_count() == 0  # the TPU is ruled out
    env = chips.one_chip_env(2, base={"KEEP": "1"})
    assert env["KEEP"] == "1" and env["TPU_VISIBLE_DEVICES"] == "2"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env != chips.one_chip_env(3, base={"KEEP": "1"})


_DEPLOY = os.path.join(
    _ROOT, "sparknet_tpu", "models", "prototxt", "cifar10_quick_deploy.prototxt"
)


def test_router_refuses_more_replicas_than_chips(monkeypatch, capsys):
    from sparknet_tpu.tools import serve
    from sparknet_tpu.utils import chips

    monkeypatch.setattr(chips, "local_chip_count", lambda: 1)
    with pytest.raises(SystemExit) as exc:
        serve.main(["--model", _DEPLOY, "--replicas", "2"])
    assert exc.value.code == 2
    assert "2 TPU chips and this host has 1" in capsys.readouterr().err


def test_router_refuses_the_deploy_loop_on_a_tpu_host(monkeypatch, capsys):
    from sparknet_tpu.tools import serve
    from sparknet_tpu.utils import chips

    monkeypatch.setattr(chips, "local_chip_count", lambda: 4)
    with pytest.raises(SystemExit):
        serve.main([
            "--model", _DEPLOY, "--replicas", "2", "--deploy-dir", "d",
            "--deploy-train-net", "t.prototxt",
        ])
    assert "one process per chip" in capsys.readouterr().err


# ------------------------------------------------- nothing hides the device

def test_unknown_tpu_kind_is_an_error():
    from sparknet_tpu.utils.profiling import device_peak_flops

    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert device_peak_flops(Dev("tpu", "TPU v5 lite")) == 197e12
    assert device_peak_flops(Dev("cpu", "cpu")) is None
    with pytest.raises(ValueError, match="TPU v9"):
        device_peak_flops(Dev("tpu", "TPU v9"))


def test_native_build_failure_is_reported_not_hidden(monkeypatch):
    from sparknet_tpu import native

    def failing_make(*a, **k):
        raise subprocess.CalledProcessError(2, a[0], stderr=b"g++: not\nfound")

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_why_not", None)
    monkeypatch.setattr(native.subprocess, "run", failing_make)
    assert not native.available()
    assert native.unavailable_reason() == "make failed: g++: not found"


def test_native_library_always_goes_through_make(monkeypatch):
    """An ``.so`` on disk is not trusted to match its source: the load
    runs ``make -C native`` (a no-op when up to date) every time."""
    from sparknet_tpu import native

    calls = []
    real_run = subprocess.run

    def spying_run(cmd, **kw):
        calls.append(cmd)
        return real_run(cmd, **kw)

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native.subprocess, "run", spying_run)
    native.available()
    assert calls and calls[0][:2] == ["make", "-C"]
