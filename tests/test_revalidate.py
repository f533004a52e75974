"""Unit tests for the TPU revalidation queue's recording logic.

The queue runs unattended on the chip; its parsing must
convert every subprocess outcome — good JSON, garbage, crashes,
timeouts — into an appended record without killing the chain. These
tests stub ``subprocess.run`` so no device (or bench) is involved.
"""

import json
import subprocess
import types

import pytest

from predictionio_tpu.tools import tpu_revalidate as tr


@pytest.fixture(autouse=True)
def evidence_file(tmp_path, monkeypatch):
    out = tmp_path / "ev.jsonl"
    monkeypatch.setattr(tr, "OUT", str(out))
    return out


def _records(path):
    return [json.loads(l) for l in path.read_text().splitlines() if l]


def _stub(monkeypatch, stdout="", stderr="", rc=0, raise_timeout=False):
    def fake_run(*a, **kw):
        if raise_timeout:
            raise subprocess.TimeoutExpired(cmd=a[0], timeout=1)
        return types.SimpleNamespace(
            stdout=stdout, stderr=stderr, returncode=rc
        )

    monkeypatch.setattr(tr.subprocess, "run", fake_run)


class TestRunBench:
    def test_good_json_recorded_with_step(self, monkeypatch, evidence_file):
        _stub(monkeypatch, stdout='noise\n{"value": 17.8, "holdout_rmse": 0.53}\n')
        rec = tr.run_bench("baseline_f32", {})
        assert rec["value"] == 17.8 and rec["step"] == "baseline_f32"
        assert _records(evidence_file)[0]["step"] == "baseline_f32"

    def test_malformed_json_recorded_not_raised(self, monkeypatch,
                                                evidence_file):
        _stub(monkeypatch, stdout='{"truncated": ', rc=1)
        rec = tr.run_bench("baseline_f32", {})
        assert "malformed" in rec["error"]
        assert _records(evidence_file)[0]["rc"] == 1

    def test_timeout_recorded_and_chain_continues(self, monkeypatch,
                                                  evidence_file):
        _stub(monkeypatch, raise_timeout=True)
        rec = tr.run_bench("bf16_gather", {}, timeout_s=1)
        assert rec["rc"] == -1 and "timed out" in rec["error"]

    def test_cpu_run_marked_invalid(self, monkeypatch, evidence_file):
        _stub(monkeypatch,
              stdout='{"value": 12.0, "platform": "cpu"}\n')
        rec = tr.run_bench("baseline_f32", {})
        assert "NOT MEASURED ON A TPU" in rec["note"]


class TestRunStep:
    def test_inner_step_name_normalized(self, monkeypatch, evidence_file):
        # _reval_steps subcommand names differ from their records' own
        # step names; the file must use ONE name per logical step
        _stub(monkeypatch,
              stdout='{"step": "fused_kernel_compiled", "ok": true}\n')
        rec = tr.run_step("fused_smoke")
        assert rec["step"] == "fused_smoke"
        assert rec["inner_step"] == "fused_kernel_compiled"
        assert rec["ok"] is True

    def test_crash_with_no_json_records_stderr_tail(self, monkeypatch,
                                                    evidence_file):
        _stub(monkeypatch, stdout="", stderr="Trace\nRuntimeError: boom",
              rc=1)
        rec = tr.run_step("mesh_pallas")
        assert rec["error"] == "RuntimeError: boom"
        assert rec["rc"] == 1

    def test_malformed_json_guarded(self, monkeypatch, evidence_file):
        _stub(monkeypatch, stdout='{"ok": tru')
        rec = tr.run_step("dispatch_bench")
        assert "malformed" in rec["error"]


class TestRecent:
    def test_append_stamps_and_recent_finds(self, evidence_file):
        tr.append({"step": "baseline_f32", "value": 17.0})
        rec = tr._recent("baseline_f32")
        assert rec["value"] == 17.0 and "t_unix" in rec

    def test_old_record_not_reused(self, evidence_file):
        import time

        tr.append({"step": "baseline_f32", "value": 17.0,
                   "t_unix": time.time() - 7 * 3600})
        assert tr._recent("baseline_f32") is None

    def test_unstamped_pre_tier_record_ignored(self, evidence_file):
        evidence_file.write_text('{"step": "baseline_f32", "value": 1}\n')
        assert tr._recent("baseline_f32") is None

    def test_newest_record_wins(self, evidence_file):
        tr.append({"step": "fused_smoke", "ok": False})
        tr.append({"step": "fused_smoke", "ok": True})
        assert tr._recent("fused_smoke")["ok"] is True

    def test_missing_file_is_none(self, evidence_file):
        assert tr._recent("anything") is None

    def test_cpu_sourced_record_not_reused(self, evidence_file):
        # a CPU-env invocation must never become the RMSE gate or
        # Mosaic verdict of a TPU run
        tr.append({"step": "baseline_f32", "rc": 0, "value": 9.0,
                   "holdout_rmse": 0.53, "device": "TFRT_CPU_0"})
        tr.append({"step": "fused_smoke", "rc": 0, "ok": True,
                   "backend": "cpu"})
        assert tr._recent("baseline_f32") is None
        assert tr._recent("fused_smoke") is None


class TestTiers:
    """Tier A runs exactly the headline records; tier B reuses
    fresh tier-A records instead of re-spending device time."""

    @pytest.fixture
    def harness(self, monkeypatch, evidence_file):
        calls = []

        def fake_bench(step, env, timeout_s=1800):
            calls.append(("bench", step))
            rec = {"step": step, "rc": 0, "value": 17.0,
                   "holdout_rmse": 0.53, "iteration_s": [1.0, 0.4],
                   "bucketize_stage_s": 2.0}
            tr.append(dict(rec))
            return rec

        def fake_step(step, timeout_s=900, env_extra=None):
            calls.append(("step", step))
            rec = {"step": step, "rc": 0, "ok": True}
            if env_extra:
                rec["lever"] = dict(env_extra)
            tr.append(dict(rec))
            return rec

        monkeypatch.setattr(tr, "run_bench", fake_bench)
        monkeypatch.setattr(tr, "run_step", fake_step)
        monkeypatch.delenv("BENCH_SCALE", raising=False)
        monkeypatch.delenv("BENCH_ITERATIONS", raising=False)
        return calls

    def _main(self, monkeypatch, argv):
        import sys as _sys

        monkeypatch.setattr(_sys, "argv", ["tpu_revalidate"] + argv)
        return tr.main()

    def test_tier_a_runs_only_golden_records(self, harness, monkeypatch):
        rc = self._main(monkeypatch, ["--tier", "a"])
        assert rc == 0
        assert harness == [("bench", "baseline_f32"),
                           ("step", "fused_smoke"),
                           ("step", "mesh_pallas")]

    def test_tier_b_reuses_fresh_tier_a_records(self, harness, monkeypatch):
        tr.append({"step": "baseline_f32", "rc": 0, "value": 17.0,
                   "holdout_rmse": 0.53, "iteration_s": [1.0, 0.4],
                   "bucketize_stage_s": 2.0, "scale": 1.0,
                   "iterations": 10})
        tr.append({"step": "fused_smoke", "rc": 0, "ok": True})
        tr.append({"step": "mesh_pallas", "rc": 0, "ok": True})
        rc = self._main(monkeypatch, ["--tier", "b", "--repeats", "1",
                                      "--skip-loadgen"])
        assert rc == 0
        bench_steps = [s for kind, s in harness if kind == "bench"]
        step_steps = [s for kind, s in harness if kind == "step"]
        assert "baseline_f32" not in bench_steps
        assert set(bench_steps) == {"bf16_gather", "sort_gather",
                                    "bf16_plus_sort", "fused_gather",
                                    "fused_plus_bf16"}
        # fused_smoke/mesh_pallas reused from the file, not re-run;
        # implicit_gate runs because bf16+sort passed their explicit gates
        assert step_steps == ["dispatch_bench", "flash_pallas",
                              "profile_trace", "implicit_gate"]

    def test_tier_b_rejects_config_mismatched_baseline(self, harness,
                                                       monkeypatch):
        # a baseline measured at a different scale/iterations must not
        # become this run's RMSE gate (review finding)
        tr.append({"step": "baseline_f32", "rc": 0, "value": 17.0,
                   "holdout_rmse": 0.53, "iteration_s": [1.0, 0.4],
                   "bucketize_stage_s": 2.0, "scale": 0.01,
                   "iterations": 10})
        rc = self._main(monkeypatch, ["--tier", "b", "--repeats", "1",
                                      "--skip-loadgen"])
        assert rc == 0
        bench_steps = [s for kind, s in harness if kind == "bench"]
        assert bench_steps[0] == "baseline_f32"  # re-measured, not reused

    def test_tier_b_rc1_when_a_step_times_out(self, harness, monkeypatch):
        # a tier B whose steps time out must NOT report complete: rc=1
        # tells the caller to run the queue again (review finding)
        def timing_out_step(step, timeout_s=900, env_extra=None):
            rec = {"step": step, "rc": -1, "error": "timed out"}
            tr.append(dict(rec))
            return rec

        monkeypatch.setattr(tr, "run_step", timing_out_step)
        rc = self._main(monkeypatch, ["--tier", "b", "--repeats", "1",
                                      "--skip-loadgen"])
        assert rc == 1

    def test_failed_tier_a_step_record_not_reused(self, harness,
                                                  monkeypatch):
        # tier A's smoke timed out; tier B must give it a fresh chance,
        # not inherit the failure (review finding)
        tr.append({"step": "baseline_f32", "rc": 0, "value": 17.0,
                   "holdout_rmse": 0.53, "iteration_s": [1.0, 0.4],
                   "bucketize_stage_s": 2.0, "scale": 1.0,
                   "iterations": 10})
        tr.append({"step": "fused_smoke", "rc": -1, "error": "timed out"})
        rc = self._main(monkeypatch, ["--tier", "b", "--repeats", "1",
                                      "--skip-loadgen"])
        assert rc == 0
        step_steps = [s for kind, s in harness if kind == "step"]
        assert "fused_smoke" in step_steps  # re-run, not reused

    def test_tier_b_standalone_runs_baseline_itself(self, harness,
                                                    monkeypatch):
        rc = self._main(monkeypatch, ["--tier", "b", "--repeats", "1",
                                      "--skip-loadgen"])
        assert rc == 0
        bench_steps = [s for kind, s in harness if kind == "bench"]
        assert bench_steps[0] == "baseline_f32"
        step_steps = [s for kind, s in harness if kind == "step"]
        assert "fused_smoke" in step_steps and "mesh_pallas" in step_steps
