"""Shared CLI surface: one flag vocabulary, one validator, one prescan.

``repro.launch.cli`` is the single declaration point for the flags the
stream/transport/fleet/workload drivers share.  The unit half exercises
the prescan and validator in-process (no jax); the subprocess half pins
``--help`` and error-exit parity across all four entry points -- same
flags advertised, same exit code 2, same pinned message for the same bad
value, regardless of which driver you typed it at.
"""
import argparse
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch.cli import (
    add_devices_arg, add_metrics_args, add_slot_table_args, add_symed_args,
    prescan_host_devices, validate_shared_args,
)

REPO = Path(__file__).resolve().parents[1]
SUBENV = {**os.environ, "PYTHONPATH": str(REPO / "src")}

CLIS = ("repro.launch.stream", "repro.launch.transport",
        "repro.launch.fleet", "repro.workload")


# ----------------------------------------------------------- prescan unit


class TestPrescan:
    def test_sets_xla_flags_for_multi_device(self, monkeypatch):
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        prescan_host_devices(["prog", "--devices", "4"])
        assert "--xla_force_host_platform_device_count=4" in \
            os.environ["XLA_FLAGS"]

    def test_equals_form_and_last_wins(self, monkeypatch):
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        prescan_host_devices(["prog", "--devices", "2", "--devices=8"])
        assert "device_count=8" in os.environ["XLA_FLAGS"]

    def test_single_device_leaves_env_alone(self, monkeypatch):
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        prescan_host_devices(["prog", "--devices", "1"])
        assert "XLA_FLAGS" not in os.environ

    def test_malformed_value_left_for_argparse(self, monkeypatch):
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        prescan_host_devices(["prog", "--devices", "many"])
        assert "XLA_FLAGS" not in os.environ

    def test_preserves_existing_flags(self, monkeypatch):
        monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
        prescan_host_devices(["prog", "--devices=2"])
        assert "device_count=2" in os.environ["XLA_FLAGS"]
        assert "--xla_foo=1" in os.environ["XLA_FLAGS"]


# ------------------------------------------------------- device meshes


def test_mesh_larger_than_the_platform_is_an_error():
    import jax

    from repro.launch.fleet import fleet_data_mesh

    have = jax.device_count()
    assert fleet_data_mesh(have).devices.size == have
    with pytest.raises(ValueError, match=f"{have + 1}-device data mesh"):
        fleet_data_mesh(have + 1)


# -------------------------------------------------------- compile cache


class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record config updates instead of turning the cache on here."""
        import jax

        seen = []
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: seen.append((name, value)))
        return seen

    def test_env_var_wins_and_code_sets_no_other_dir(self, monkeypatch,
                                                     tmp_path, updates):
        from repro.utils.compile_cache import ENV_VAR, enable_compile_cache

        monkeypatch.setenv(ENV_VAR, str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert updates == []

    def test_unset_uses_the_fixed_dir_in_the_checkout(self, monkeypatch,
                                                      updates):
        from repro.utils.compile_cache import ENV_VAR, enable_compile_cache

        monkeypatch.delenv(ENV_VAR, raising=False)
        path = REPO / ".jax_cache"
        assert enable_compile_cache() == str(path)
        assert enable_compile_cache() == str(path)  # no pid, time or temp
        assert updates == [("jax_compilation_cache_dir", str(path))] * 2
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored


# --------------------------------------------------------- validator unit


def _full_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, default=6)
    ap.add_argument("--length", type=int, default=384)
    ap.add_argument("--window", type=int, default=48)
    add_slot_table_args(ap, max_slots=4)
    add_devices_arg(ap)
    add_symed_args(ap)
    add_metrics_args(ap)
    return ap


BAD_ARGS = [
    (["--sessions", "0"], "--sessions must be >= 1, got 0"),
    (["--length", "1"], "--length must be >= 2, got 1"),
    (["--window", "0"], "--window must be >= 1, got 0"),
    (["--window", "500"], "--window 500 exceeds --length 384"),
    (["--digitize-every", "-1"], "--digitize-every must be >= 0, got -1"),
    (["--tol", "-1"], "--tol must be > 0, got -1.0"),
    (["--alpha", "2"], "--alpha must be in (0, 1], got 2.0"),
    (["--devices", "0"], "--devices must be >= 1, got 0"),
    (["--max-slots", "0"], "--max-slots must be >= 1, got 0"),
    (["--max-slots", "6", "--devices", "4"],
     "--max-slots 6 must divide over --devices 4"),
    (["--min-slots", "9"], "--min-slots 9 must be in [1, --max-slots 4]"),
    (["--max-slots", "8", "--min-slots", "3", "--devices", "2"],
     "--min-slots 3 must divide over --devices 2"),
    (["--shrink-patience", "0"], "--shrink-patience must be >= 1, got 0"),
    (["--metrics-port", "70000"],
     "--metrics-port must be in [0, 65535], got 70000"),
    (["--metrics-linger", "-1"], "--metrics-linger must be >= 0, got -1.0"),
]


class TestSharedValidator:
    def test_good_args_pass(self):
        ap = _full_parser()
        validate_shared_args(ap, ap.parse_args([]))  # defaults are valid
        validate_shared_args(ap, ap.parse_args(
            ["--devices", "4", "--max-slots", "8", "--min-slots", "4",
             "--metrics-port", "0"]))

    @pytest.mark.parametrize("argv,message", BAD_ARGS,
                             ids=[" ".join(a) for a, _ in BAD_ARGS])
    def test_bad_args_exit_2_with_pinned_message(self, argv, message,
                                                 capsys):
        ap = _full_parser()
        with pytest.raises(SystemExit) as exc:
            validate_shared_args(ap, ap.parse_args(argv))
        assert exc.value.code == 2
        assert message in capsys.readouterr().err

    def test_partial_namespace_skips_absent_flags(self):
        # fleet has no --max-slots; a namespace without it must validate
        ap = argparse.ArgumentParser()
        ap.add_argument("--streams", type=int, default=8)
        add_devices_arg(ap, default=8)
        add_symed_args(ap)
        validate_shared_args(ap, ap.parse_args([]))
        with pytest.raises(SystemExit):
            validate_shared_args(ap, ap.parse_args(["--streams", "0"]))


# ------------------------------------------------------ subprocess parity


def _run_cli(module, argv):
    return subprocess.run(
        [sys.executable, "-m", module, *argv], capture_output=True,
        text=True, env=SUBENV, cwd=REPO, timeout=300)


@pytest.mark.slow
class TestCLIParity:
    @pytest.mark.parametrize("module", CLIS)
    def test_help_exits_zero_and_advertises_shared_flags(self, module):
        proc = _run_cli(module, ["--help"])
        assert proc.returncode == 0, proc.stderr[-2000:]
        for flag in ("--devices", "--tol", "--alpha", "--seed"):
            assert flag in proc.stdout, (module, flag)
        if module != "repro.workload":
            for flag in ("--metrics-port", "--metrics-linger", "--trace-out"):
                assert flag in proc.stdout, (module, flag)
        if module in ("repro.launch.stream", "repro.launch.transport"):
            for flag in ("--max-slots", "--min-slots", "--autoscale",
                         "--shrink-patience", "--pretrace"):
                assert flag in proc.stdout, (module, flag)

    @pytest.mark.parametrize("module,argv,message", [
        ("repro.launch.stream", ["--tol", "-1"],
         "--tol must be > 0, got -1.0"),
        ("repro.launch.transport", ["--metrics-port", "70000"],
         "--metrics-port must be in [0, 65535], got 70000"),
        ("repro.launch.fleet", ["--devices", "0"],
         "--devices must be >= 1, got 0"),
        ("repro.workload", ["--scenario", "flash_crowd", "--sessions", "0"],
         "--sessions must be >= 1, got 0"),
    ], ids=[c.rsplit(".", 1)[-1] for c in CLIS])
    def test_bad_value_rejected_identically(self, module, argv, message):
        proc = _run_cli(module, argv)
        assert proc.returncode == 2, (proc.returncode, proc.stderr[-2000:])
        assert message in proc.stderr

    def test_workload_rejects_unknown_slo(self):
        proc = _run_cli("repro.workload",
                        ["--scenario", "flash_crowd", "--slo", "bogus=1"])
        assert proc.returncode == 2
        assert "unknown SLO" in proc.stderr

    def test_stream_workload_and_pattern_are_exclusive(self):
        proc = _run_cli("repro.launch.stream",
                        ["--workload", "flash_crowd",
                         "--arrival-pattern", "bursty"])
        assert proc.returncode == 2
        assert "mutually exclusive" in proc.stderr
