"""Chip bring-up contract (PR 21), checked on the CPU.

What keeps a later run from passing without the chip: the compile cache can
be placed from outside and never moves on its own; ``chip_smoke.py`` and
``bench.py`` refuse to run without a TPU backend; nothing measures on a CPU
nobody asked for; utilization peaks come from a ``device_kind``-keyed table
that raises on an unknown device; tool parents that spawn chip-owning
children stay off the jax backend; failed points make exit codes nonzero.
"""

import json
import os
import subprocess
import sys
import time
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd=REPO, **env):
    e = {k: v for k, v in os.environ.items()
         if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    e.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO, **env})
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, *argv], cwd=cwd, env=e,
                       capture_output=True, text=True, timeout=300)
    return r, time.monotonic() - t0


# -- compile cache placement -------------------------------------------------


def _record_config_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return calls


def test_cache_dir_from_env_sets_no_directory_in_code(monkeypatch, tmp_path):
    from ddlbench_tpu.distributed import enable_compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _record_config_updates(monkeypatch)
    assert enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in calls
    # the size/time knobs stay
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 1.0
    assert calls["jax_persistent_cache_min_entry_size_bytes"] == 0


def test_cache_dir_default_is_fixed_path_in_checkout(monkeypatch):
    from ddlbench_tpu.distributed import enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_config_updates(monkeypatch)
    want = os.path.join(REPO, ".jax_cache")
    assert enable_compilation_cache() == want
    assert calls["jax_compilation_cache_dir"] == want
    assert enable_compilation_cache() == want  # stable across calls
    # ... and across pids: another process derives the same directory and
    # jax ends up configured with it
    r, _ = _run(["-c", "import jax\n"
                 "from ddlbench_tpu.distributed import "
                 "enable_compilation_cache as e\n"
                 "print(e()); print(jax.config.jax_compilation_cache_dir)"])
    assert r.stdout.split() == [want, want], (r.stdout, r.stderr[-500:])
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_every_entry_point_enables_the_cache():
    """cli.main had no persistent cache at all before PR 21."""
    import inspect

    from ddlbench_tpu import cli
    from ddlbench_tpu.tools import servebench

    for mod in (cli, servebench):
        assert "enable_compilation_cache()" in inspect.getsource(mod.main)
    for script in ("bench.py", "chip_smoke.py"):
        with open(os.path.join(REPO, script)) as f:
            assert "enable_compilation_cache()" in f.read()


# -- no chip, no result -------------------------------------------------------


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_script_refuses_cpu_quickly(script):
    r, secs = _run([script])
    assert r.returncode != 0
    assert "TPU backend" in r.stderr
    # no result line, no step taken
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
    assert "train |" not in r.stdout
    assert secs < 120


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    r, _ = _run(["chip_smoke.py"], cwd=str(tmp_path), PYTHONPATH="")
    assert r.returncode != 0 and not r.stdout.strip()


def test_chip_smoke_is_one_process_with_no_cpu_branch():
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]  # past the module docstring
    for banned in ("subprocess", "interpret", "--platform", "Popen"):
        assert banned not in code, banned


def test_chip_smoke_last_line_is_the_verdict_and_nothing_else():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = mod.verdict_line(True, [dev])
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    # the verdict is the last thing main() prints; the report goes before it
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        body = f.read().split("def main()", 1)[1]
    assert body.rindex("print(") == body.index("print(verdict_line(")


def _fake_jax(backend, platforms):
    return types.SimpleNamespace(
        default_backend=lambda: backend, device_count=lambda: 1,
        config=types.SimpleNamespace(jax_platforms=platforms))


def test_unrequested_cpu_is_an_error_not_a_banner(monkeypatch):
    from ddlbench_tpu import distributed

    monkeypatch.setattr(distributed, "jax", _fake_jax("cpu", None))
    with pytest.raises(SystemExit, match="refusing to measure"):
        distributed.backend_provenance(None, "sometool")
    with pytest.raises(SystemExit):
        distributed.record_provenance()
    # asked for, either way of asking: a row, marked as requested
    assert distributed.backend_provenance("cpu")["cpu_requested"]
    monkeypatch.setattr(distributed, "jax", _fake_jax("cpu", "cpu"))
    prov = distributed.record_provenance(None, "sometool")
    assert prov["cpu_requested"] and not prov["cpu_fallback"]
    assert prov["schema_version"] == distributed.RECORD_SCHEMA_VERSION
    monkeypatch.setattr(distributed, "jax", _fake_jax("tpu", None))
    assert distributed.backend_provenance()["jax_backend"] == "tpu"
    assert not hasattr(distributed, "warn_cpu_fallback")


@pytest.mark.parametrize("backend,want", [
    ("tpu", True), ("tpu_plugin", False), ("cpu", False), ("gpu", False)])
def test_is_tpu_backend_only_for_tpu(monkeypatch, backend, want):
    from ddlbench_tpu.distributed import is_tpu_backend

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert is_tpu_backend() is want


def test_peaks_table_keyed_by_device_kind_raises_on_unknown():
    from ddlbench_tpu.config import DEVICE_PEAKS, device_peaks

    v5e = device_peaks("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bandwidth) == (197e12, 819e9)
    for kind in ("TPU v4", "cpu", "tpu", ""):
        assert kind not in DEVICE_PEAKS
        with pytest.raises(KeyError, match="no published peaks"):
            device_peaks(kind)


def test_bench_has_no_probe_fallback_or_stale_carryover():
    import bench

    for gone in ("_device_probe", "_last_known_onchip", "subprocess"):
        assert not hasattr(bench, gone), gone
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "probe-timeout" not in src and "cpu-fallback" not in src
    assert "device_peaks(device.device_kind)" in src


def test_memory_stats_failure_shows_on_tpu_only():
    from ddlbench_tpu.train.metrics import device_memory_gb

    assert device_memory_gb()["in_use"] == 0.0  # CPU keeps no statistics
    silent_tpu = types.SimpleNamespace(platform="tpu",
                                       memory_stats=lambda: None)
    with pytest.raises(RuntimeError, match="memory_stats"):
        device_memory_gb(silent_tpu)
    tpu = types.SimpleNamespace(
        platform="tpu", memory_stats=lambda: {"bytes_in_use": 2**30})
    assert device_memory_gb(tpu)["in_use"] == 1.0


def test_mesh_construction_failures_raise(monkeypatch, devices):
    from jax.experimental import mesh_utils

    from ddlbench_tpu.distributed import make_mesh

    def boom(*a, **k):
        raise RuntimeError("no such topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", boom)
    with pytest.raises(RuntimeError, match="no such topology"):
        make_mesh([("data", 4)])


def test_comm_stats_unmodelled_strategy_is_named_not_swallowed():
    from ddlbench_tpu.train.comm_stats import comm_stats

    class SPStrategy:  # sp/tp/fsdp/ep have no analytic model
        pass

    with pytest.raises(NotImplementedError, match="SPStrategy"):
        comm_stats(SPStrategy())


# -- one process per chip ------------------------------------------------------

_PARENTS_SCRIPT = r"""
import json, subprocess, sys
from jax._src import xla_bridge
seen = {}

class FakeRun:
    returncode = 0
    stderr = ""
    stdout = json.dumps({"engine": "x", "samples_per_sec": 1.0,
                         "platform": "tpu"})

def fake_run(argv, **kw):
    seen.setdefault("hetero_spawns", []).append(
        xla_bridge.backends_are_initialized())
    return FakeRun()

subprocess.run = fake_run
from ddlbench_tpu.tools import heterobench
seen["hetero_rc"] = heterobench.main(["--plan", "1,1", "--uneven", ""])
seen["hetero_after"] = xla_bridge.backends_are_initialized()

from ddlbench_tpu.tools import chaosbench

def fake_attempt(argv, log_path):
    seen.setdefault("chaos_spawns", []).append(
        xla_bridge.backends_are_initialized())
    res = chaosbench.AttemptResult()
    res.rc, res.wall_s = 0, 1.0
    res.lines = ['result: ' + json.dumps(
        {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}})]
    return res

chaosbench._run_attempt = fake_attempt
args = chaosbench._parse_args(
    ["--kills", "0", "--skip-verify", "--workdir", sys.argv[1]])
seen["chaos_device"] = chaosbench.run_chaos(args)["device"]
seen["chaos_after"] = xla_bridge.backends_are_initialized()
print(json.dumps(seen))
"""


@pytest.fixture(scope="module")
def parents(tmp_path_factory):
    work = tmp_path_factory.mktemp("chaos")
    r, _ = _run(["-c", _PARENTS_SCRIPT, str(work)])
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_heterobench_parent_stays_off_the_backend(parents):
    assert parents["hetero_rc"] == 0
    assert parents["hetero_spawns"] == [False, False]  # hetero, grid
    assert parents["hetero_after"] is False


def test_chaosbench_parent_stays_off_the_backend(parents):
    assert parents["chaos_spawns"] and not any(parents["chaos_spawns"])
    assert parents["chaos_after"] is False
    # provenance comes from the child's result line
    assert parents["chaos_device"]["kind"] == "TPU v5 lite"


def test_accparity_parent_has_no_backend_touch():
    """accparity's parent only exports data and spawns CLI children."""
    import inspect

    from ddlbench_tpu.tools import accparity

    src = inspect.getsource(accparity)
    assert "import jax" not in src and "jax." not in src.replace(
        "jax.config", "")


# -- failures make exit codes nonzero ------------------------------------------


def test_heterobench_short_of_devices_is_nonzero(capsys):
    from ddlbench_tpu.tools import heterobench

    rc = heterobench.main(["--in-process", "--plan", "8,8", "--uneven", "",
                           "--platform", "cpu"])
    assert rc != 0
    assert "needs 16 devices" in capsys.readouterr().out


def test_heterobench_failed_child_is_nonzero(monkeypatch, capsys):
    from ddlbench_tpu.tools import heterobench

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: types.SimpleNamespace(
        returncode=1, stdout="", stderr="RuntimeError: chip is busy"))
    assert heterobench.main(["--plan", "1,1", "--uneven", ""]) != 0
    out = capsys.readouterr().out
    assert "chip is busy" in out and "comparison" not in out


def test_scalebench_failed_point_is_nonzero(monkeypatch, capsys):
    from ddlbench_tpu.tools import scalebench

    def run_point(cfg, *a):
        if cfg.strategy != "single":
            raise RuntimeError("point exploded")
        return 100.0, 0

    monkeypatch.setattr(scalebench, "_run_point", run_point)
    rc = scalebench.main(["-b", "mnist", "-m", "lenet", "--devices", "2",
                          "--strategies", "dp", "--platform", "cpu"])
    assert rc != 0
    assert "point exploded" in capsys.readouterr().out


# -- one paged-kernel style, found by name in compiled programs ------------------


@pytest.mark.parametrize("tool", ["servebench", "decodebench"])
def test_paged_kernel_flag_is_gone(tool):
    import importlib

    mod = importlib.import_module(f"ddlbench_tpu.tools.{tool}")
    with pytest.raises(SystemExit):
        mod.main(["--paged-kernel", "dots"])
    from ddlbench_tpu.ops import paged_decode

    assert not hasattr(paged_decode, "set_paged_kernel_style")


@pytest.mark.parametrize("backward,count", [
    ("flash_attn_dq", {"flash_attn_dq": 1}),
    # the one-pass backward's name holds the two-kernel pair's as substrings;
    # the manifest has to count it under its own
    ("flash_attn_dq_dkv", {"flash_attn_dq_dkv": 1}),
])
def test_pallas_kernels_read_from_compiled_hlo(backward, count):
    from ddlbench_tpu.telemetry.audit import pallas_kernels

    hlo = '''
  %a = bf16[2] custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(BACKWARD))/pallas_call" stack_frame_id=3}
  %b = bf16[2] custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(flash_attn_fwd)/pallas_call"}
  %c = f32[2] custom-call(%y), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_fn)/paged_decode_attn/pallas_call"}
  %d = f32[2] custom-call(%y), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_fn)/paged_decode_attn/pallas_call"}
  %e = f32[2] custom-call(%y), custom_call_target="Sharding", metadata={op_name="jit(f)/x"}
'''.replace("BACKWARD", backward)
    assert pallas_kernels(hlo) == {**count, "flash_attn_fwd": 1,
                                   "paged_decode_attn": 2}
    # a CPU program (reference / interpret paths) holds none
    assert pallas_kernels(jax.jit(lambda x: x * 2).lower(1.0).compile()
                          .as_text()) == {}


def test_timing_barrier_is_block_until_ready():
    import inspect

    from ddlbench_tpu.tools import timing

    assert "float(" not in inspect.getsource(timing)
    steps = []
    dt = timing.timed_steps(lambda x, y: steps.append(x) or {"loss": x},
                            lambda e, s: (jax.numpy.float32(s), 0), 3, 1)
    assert dt > 0 and len(steps) == 4  # one warmup + three timed
