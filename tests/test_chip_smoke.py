"""chip_smoke.py on the CPU: the explicit dry run passes and reports the
contract's fields, the default refuses a CPU backend, the compile cache
lands where it was placed, and the HTTP-only CLI stays off JAX."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _report_and_verdict(out: str) -> tuple:
    """The last two stdout lines: the full report, then the verdict."""
    report, verdict = out.strip().splitlines()[-2:]
    return json.loads(report), json.loads(verdict)


def test_cpu_dry_run_passes_and_reports(capsys):
    import jax

    assert chip_smoke.main(["--cpu-dry-run", "--seed", "3"]) == 0
    rep, verdict = _report_and_verdict(capsys.readouterr().out)
    # the last line carries exactly the contract's keys
    assert verdict == {"ok": True, "device": rep["device"]}
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert rep["ok"] is True and "failures" not in rep
    assert rep["device"] == {"platform": "cpu",
                             "kind": jax.devices()[0].device_kind,
                             "count": jax.device_count()}
    assert rep["sizes"] == chip_smoke.TINY and rep["seed"] == 3
    leg = rep["legs"]["served"]
    # conftest's 8 virtual devices: the default path is the sharded one
    assert leg["mesh_devices"] == jax.device_count()
    from nomad_tpu.parallel.mesh import PROGRAM_NAMES
    assert {"place_multi_compact_sharded",
            "place_multi_compact_sharded_chained", "place_sharded_packed",
            "scatter_add_sharded"} <= set(leg["sharded_programs"]) <= set(
                PROGRAM_NAMES)
    n_batch = chip_smoke.TINY["jobs"] + 2 * chip_smoke.ZONES
    assert leg["evals"] == {"complete": n_batch + 1}
    assert leg["placed"] == leg["asked"] == (
        n_batch * chip_smoke.TINY["per_job"]
        + chip_smoke.TINY["spread_count"])
    assert leg["executor"]["dispatches"] >= 2
    assert leg["executor"]["resident_waves"] >= 1
    assert leg["executor"]["upload_bytes_by_cause"][
        "invalidation-replay"] > 0
    assert leg["workers"][0]["nacked"] == 0
    assert leg["node_tensor_platforms"] == ["cpu"]
    assert set(leg["smoke_observations"]["phase_wall_s"]) == {
        "fleet", "register", "schedule", "readback", "check"}
    sites = rep["smoke_observations"]["first_launch_s"]
    assert any(s.startswith("engine.multi_compact/") for s in sites)
    assert any(s.startswith("engine.multi_compact_chained/")
               for s in sites)
    assert set(rep["compile_cache"]) == {"dir", "entries_before",
                                         "entries_after"}


def test_scan_fused_leg_dry_run_agrees_and_reports_no_device_time(capsys):
    """`--scan-fused --cpu-dry-run`: spread5k's eval, tiny, through both
    scans (the kernel in Pallas' interpreter): every row equal, every
    step placed, and no device time from a CPU run."""
    assert chip_smoke.main(["--scan-fused", "--cpu-dry-run",
                            "--seed", "2147939001"]) == 0
    rep, verdict = _report_and_verdict(capsys.readouterr().out)
    assert verdict == {"ok": True, "device": rep["device"]}
    leg = rep["scan_fused"]
    assert leg["sizes"] == chip_smoke.SCAN_TINY
    assert (leg["rows_differing"], leg["picks_differing"]) == (0, 0)
    assert leg["rows_equal"] == chip_smoke.SCAN_TINY["p_pad"]
    assert leg["final_state_equal"] and leg["differing_columns"] == {}
    assert leg["placed"] == chip_smoke.SCAN_TINY["steps"]
    assert leg["device_ms_per_launch"] == leg["device_us_per_step"] == {}
    assert set(leg["host_ms_per_blocked_launch"]) == {"xla", "fused"}


def test_default_refuses_a_cpu_backend(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert not any(line.startswith("{") for line in out.splitlines())


def _run(code: str, **env) -> str:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=full, check=True,
        capture_output=True, text=True, timeout=120).stdout.strip()


_CACHE_DIR = ("import nomad_tpu.ops, jax; "
              "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_placed_by_environment():
    assert _run(_CACHE_DIR, JAX_COMPILATION_CACHE_DIR="/x") == "/x"


def test_compile_cache_defaults_to_checkout():
    assert _run(_CACHE_DIR) == os.path.join(REPO, ".jax_cache")


def test_http_only_cli_never_imports_jax():
    """`job run|status` run beside a live agent that holds the chip:
    drive both as far as the (refused) connection."""
    out = _run(
        "import sys\n"
        "from nomad_tpu.cli import main\n"
        "for argv in (['job', 'status', 'web'],\n"
        "             ['job', 'run', 'examples/web.hcl']):\n"
        "    assert main(['-address', 'http://127.0.0.1:9'] + argv) == 1\n"
        "print('jax' in sys.modules)\n")
    assert out == "False"
