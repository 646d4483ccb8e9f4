"""PR 21 bring-up pins, all cheap (no model compiles): the compile-cache
helper, chip_smoke.py's refusals, the Pallas interpret rule, and the serving
child's platform discipline."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_PROBE = """
import json, os, jax
from ddw_tpu.utils.compile_cache import enable_compile_cache
first = enable_compile_cache()
print(json.dumps({"first": first, "second": enable_compile_cache(),
                  "config": jax.config.jax_compilation_cache_dir,
                  "env": os.environ.get("JAX_COMPILATION_CACHE_DIR")}))
"""


def _cache_probe(tmp_path, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    if not env_extra:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_the_variable_when_set(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX read it at import and the helper
    sets nothing else — one directory, the one the environment named."""
    want = str(tmp_path / "elsewhere")
    got = _cache_probe(tmp_path, JAX_COMPILATION_CACHE_DIR=want)
    assert got == {"first": want, "second": want, "config": want,
                   "env": want}
    assert not os.path.exists(want)  # the helper itself writes nothing


def test_compile_cache_fixed_path_when_unset(tmp_path):
    """Unset: one fixed directory inside the checkout, whatever the cwd —
    identical across two calls and two processes (the path is part of the
    cache key), set through jax.config and exported for children."""
    want = os.path.join(REPO, ".jax_cache")
    for _ in range(2):
        got = _cache_probe(tmp_path)
        assert got == {"first": want, "second": want, "config": want,
                       "env": want}


def _smoke(*args, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    cmd = ([sys.executable, "-c", code] if code
           else [sys.executable, os.path.join(REPO, "chip_smoke.py")])
    return subprocess.run(cmd + list(args), env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_a_cpu_before_compiling():
    """No rehearsal argument on the CPU: nonzero exit naming the platform
    found, before any table is written or program compiled; the device line
    comes first and no result line is printed."""
    out = _smoke()
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    assert lines[0].startswith("chip_smoke[chip]: platform=cpu ")
    assert "libtpu=" in lines[0] and len(lines) == 1   # nothing ran
    assert "found 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_leg_that_raises_exits_nonzero():
    """No leg is wrapped in a handler: one that raises ends the run with
    its traceback and a nonzero status, and nothing after it prints."""
    out = _smoke("--rehearsal", code=(
        "import sys, chip_smoke\n"
        "def boom(*a, **k): raise RuntimeError('leg exploded')\n"
        "chip_smoke.vision_leg = boom\n"
        "sys.exit(chip_smoke.main(sys.argv[1:]))\n"))
    assert out.returncode != 0
    assert "leg exploded" in out.stderr
    assert "legs_passed" not in out.stdout and '"ok"' not in out.stdout


@pytest.mark.slow   # the whole smoke at rehearsal size: ~90 s on 8 cores
def test_chip_smoke_rehearsal_passes_and_never_claims_the_chip():
    out = _smoke("--rehearsal")
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(l) for l in out.stdout.strip().splitlines()[1:]]
    assert [r["leg"] for r in rows if "leg" in r][:3] == [
        "vision_trainer", "lm_trainer", "serving_engine_tp1"]
    assert rows[-1] == {"rehearsal": True, "legs_passed": True,
                        "device": rows[-1]["device"]}
    assert rows[-1]["device"]["platform"] == "cpu"
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("some_plugin", False)])
def test_interpret_only_on_the_cpu_backend(monkeypatch, backend, want):
    """The one rule (ddw_tpu/ops/backend.py): Pallas kernels run interpreted
    when the default backend is 'cpu' and on no other — a backend with any
    other name gets the compiler, which compiles or raises."""
    import jax

    from ddw_tpu.ops import backend as rule

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert rule.interpret_by_default() is want
    # the three kernels resolve their default through that same function
    flash = sys.modules["ddw_tpu.ops.flash_attention"]
    assert flash._resolve_defaults(None, None, 64)[1] is want
    import ddw_tpu.ops.depthwise_conv as dw
    import ddw_tpu.ops.ring_reduce as ring
    assert (dw.interpret_by_default is ring.interpret_by_default
            is flash.interpret_by_default is rule.interpret_by_default)


class _DeadProc:
    pid = 0

    def wait(self):
        return 0

    def poll(self):
        return 0


def test_serving_child_platform_is_never_defaulted(tmp_path, monkeypatch):
    """ProcessReplica hands its child the parent's environment as it is: no
    JAX_PLATFORMS appears when none was set (the chip machine), and the
    tests' own cpu choice passes through."""
    from jax._src import xla_bridge

    from ddw_tpu.deploy.process_replica import ProcessReplica

    monkeypatch.setattr(xla_bridge, "_backends", {})
    seen = []
    rep = ProcessReplica(str(tmp_path / "pkg"), workdir=str(tmp_path))
    monkeypatch.setattr(rep.transport, "stage", lambda d: d)
    monkeypatch.setattr(rep.transport, "popen",
                        lambda cmd, env, log_path: seen.append(env)
                        or _DeadProc())
    monkeypatch.delenv("JAX_PLATFORMS")
    rep._spawn()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rep._spawn()
    assert "JAX_PLATFORMS" not in seen[0]
    assert seen[1]["JAX_PLATFORMS"] == "cpu"


def test_parent_that_holds_the_chip_cannot_spawn_a_serving_child(
        tmp_path, monkeypatch):
    """One process per chip: a parent that has initialised a non-CPU backend
    gets a prompt, explained failure — not a child that hangs on the device
    or quietly serves from the CPU."""
    from jax._src import xla_bridge

    from ddw_tpu.deploy.process_replica import ProcessReplica

    rep = ProcessReplica(str(tmp_path / "pkg"), workdir=str(tmp_path))
    monkeypatch.setattr(rep.transport, "popen",
                        lambda *a, **k: pytest.fail("child was spawned"))
    monkeypatch.setattr(xla_bridge, "_backends",
                        {"tpu": object(), "cpu": object()})
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="holds the device"):
        rep.start()
    # a child pinned to the CPU by the environment needs no chip
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rep._refuse_if_parent_holds_device()


def test_native_build_failure_is_fatal_and_says_why(tmp_path):
    """A library g++ refuses raises with the compiler's own message; it
    latches (no second compiler run) and nothing returns a stand-in that
    would let a caller drop to a slower Python path."""
    from ddw_tpu.native.build import LazyLibrary, NativeBuildError

    src = tmp_path / "broken.cpp"
    src.write_text("int f( { this is not C++ }\n")
    lib = LazyLibrary(str(src), str(tmp_path / "libbroken.so"))
    with pytest.raises(NativeBuildError, match="error") as first:
        lib.load()
    assert "broken.cpp" in str(first.value)
    src.write_text("extern \"C\" int f() { return 1; }\n")  # too late: latched
    with pytest.raises(NativeBuildError) as second:
        lib.load()
    assert second.value is first.value
