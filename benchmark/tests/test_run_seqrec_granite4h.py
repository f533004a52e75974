"""The Granite 4.0-H sequence-training kind off the chip, through
``benchmark/run.py``'s own ``main`` with the rehearsal workload: sound, it
says ``correct``; with the state, Delta and the decay's running sums in
bfloat16, with a history allowed to see its neighbour, with a state that
survives a boundary, with a multiplier dropped, or with a step that does not
learn, it says not. And the counts behind its roofline metrics."""

import dataclasses
import json

import pytest

from benchmark import run as bench_run
from benchmark.lib import manifest, rooflines, rooflines_granite4h
from benchmark.readers import seq_model_roofline, seq_scope_time

ARGS = ("--workload", "rehearse-train-seqrec-granite4h", "--seconds", "1")
READINGS = {
    "loss_err", "logit_err", "grad_err.ssm", "grad_err.attention", "grad_err.mlp",
    "grad_err.norms", "grad_err.embed", "ssd_err", "update_err", "loss_last_over_first",
    "window_compiles", "router_counters", "finite"}
NEEDS = {"lib": "rooflines_granite4h", "needs": "mamba_d_state"}
CELL = "train-granite4h-packed"
SHARES = ({"model": "step", "program": "^jit_step"}, {"model": "ssd_scan", "scope": "seq.ssm.scan"},
          {"model": "gqa_core", "scope": "seq.attn.core"})
SCAN_PARTS = tuple(f"seq.ssm.scan.{part}" for part in ("prep", "local", "state", "out"))


def result_of(capsys, *argv):
    assert bench_run.main(list(ARGS + argv)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def not_ok(lines):
    return {l.split()[2].rstrip(":") for l in lines if "NOT OK" in l}


def broken(capsys, monkeypatch, module, name, replacement, seed="5"):
    """One whole run with ``module.name`` replaced in the program."""
    from predictionio_tpu.models import sequencerec

    monkeypatch.setattr(module, name, replacement)
    sequencerec._programs.cache_clear()  # programs traced before the break
    try:
        return result_of(capsys, "--seed", seed, "--trace", "0")
    finally:
        sequencerec._programs.cache_clear()


def test_rehearsal_is_correct_and_prints_every_number(capsys):
    result, lines = result_of(capsys, "--seed", "3000000019", "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and result["metrics"] == {}
    assert set(result["rehearsed"]) == {"train_s", "setup_s"}
    compared = {l.split()[2].rstrip(":") for l in lines if l.startswith("[bench] compared ")}
    assert compared == READINGS
    assert any(l.startswith('[bench] mixers: {"gqa": 1, "mamba2": 9}') for l in lines)
    counters = json.loads(next(l for l in lines if l.startswith("[bench] counters: "))[18:])
    assert counters["ssd_scan"] == "xla" and 0 < counters["pack_fill_pct"] <= 100


def test_traced_rehearsal_reads_the_spans_and_counters(capsys):
    result, lines = result_of(capsys, "--seed", "7", "--trace", "1")
    assert result["correct"] is True
    assert any("4 steps each" in l for l in lines)
    # (the three ``setup_*`` metrics read a process's FIRST job alone: not here)
    assert set(result["rehearsed"]) >= {
        "step_ms", "pack_s", "input_wait_s", "fetch_s", "pack_fill_pct"}
    # nothing of an expert layer is asked of this cell
    assert not [name for name in manifest.metrics_of(CELL, True) if "moe" in name or "expert" in name]


def test_control_is_not_correct_by_the_scan_and_the_step_alone(capsys):
    result, lines = result_of(capsys, "--seed", "5", "--trace", "0", "--control", "bf16_state")
    assert result["correct"] is False
    # ssd_err: the reading taken from what the timed function's own scan was handed and
    # gave; update_err: its step starts from another gradient (the limit lies between the
    # sound and the control readings, as on the chip); every other number stands above it
    assert not_ok(lines) == {"ssd_err", "update_err"}


def test_a_history_that_sees_its_neighbour_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.models import seq_backbone as bb

    real = bb.hidden_states

    def merged(cfg, params, tokens, seg, *args, **kwargs):
        return real(cfg, params, tokens, seg * 0 + 1, *args, **kwargs)

    result, lines = broken(capsys, monkeypatch, bb, "hidden_states", merged)
    assert result["correct"] is False and result["failed"] == result["attempted"]
    # the state and the taps reached into the neighbour too: the scan itself is another
    assert {"logit_err", "grad_err.ssm", "ssd_err"} <= not_ok(lines)


def test_a_state_that_survives_a_boundary_is_not_correct(capsys, monkeypatch):
    """The scan alone told that a row is one history (the convolution and
    attention still see the boundaries): ``ssd_err`` and the network fail."""
    from predictionio_tpu.ops import ssd

    real = ssd.ssd_scan
    result, lines = broken(
        capsys, monkeypatch, ssd, "ssd_scan",
        lambda u, dt, a, b, c, seg, **kw: real(u, dt, a, b, c, seg * 0 + 1, **kw))
    assert result["correct"] is False and {"ssd_err", "grad_err.ssm", "logit_err"} <= not_ok(lines)


def test_a_dropped_multiplier_is_not_correct(capsys, monkeypatch):
    """The residual multiplier forgotten (every layer adds its whole
    output): the scan is sound on what it was handed, the network is not."""
    from predictionio_tpu.models import seq_backbone as bb

    result, lines = broken(capsys, monkeypatch, bb, "_add", lambda cfg, x, y: x + y)
    assert result["correct"] is False
    assert {"logit_err", "loss_err", "grad_err.mlp"} <= not_ok(lines) and "ssd_err" not in not_ok(lines)


def test_a_step_that_does_not_learn_is_not_correct(capsys, monkeypatch):
    import optax

    real = optax.adamw
    result, lines = broken(capsys, monkeypatch, optax, "adamw", lambda rate: real(0.0))
    assert result["correct"] is False
    assert {"loss_last_over_first", "update_err"} <= not_ok(lines)


def test_a_learning_rate_three_per_cent_off_is_not_correct(capsys, monkeypatch):
    """What ``update_err`` is held so close for: the loss still falls, the
    gradients are sound, and the step is 3 % too long."""
    import optax

    real = optax.adamw
    result, lines = broken(capsys, monkeypatch, optax, "adamw", lambda rate: real(1.03 * rate))
    assert result["correct"] is False and not_ok(lines) == {"update_err"}


def test_the_parent_program_is_refused_at_once(capsys, monkeypatch):
    """On a program whose backbone knows no ``mamba`` layer the kind ends
    the run before any job, with a message."""
    from predictionio_tpu.models import seq_backbone as bb

    old = dataclasses.make_dataclass("BackboneConfig", [("hidden_size", int, 64)])
    monkeypatch.setattr(bb, "BackboneConfig", old)
    with pytest.raises(SystemExit) as stopped:
        bench_run.main(list(ARGS + ("--seed", "1", "--trace", "0")))
    assert "no Mamba-2" in str(stopped.value.code)


def test_the_cell_is_the_issues_letter_for_letter():
    cell, cfg = manifest.workload(CELL), manifest.config("seqrec-granite4h-micro-vp8")
    assert (cell["config"], cell["kind"], cell["traffic"], cell["chips"]) == (
        "seqrec-granite4h-micro-vp8", "train_seqrec_granite4h", "packed-histories-1row", 1)
    lfm2 = manifest.workload("train-lfm2-packed8k")["traffic_params"]
    assert cell["traffic_params"] == {**lfm2, "rows_per_step": 1}  # the first sequence cell's generator and parameters
    assert (cell["traffic_params"]["warm_steps"], cell["traffic_params"]["trace_steps"]) == (2, 4)
    assert (cfg["algorithm"]["batch_size"], cfg["algorithm"]["seq_len"], cfg["vocab_size"]) == (1, 8192, 12544)
    assert set(cfg["control"]["train"]) == {"bf16_state"}
    assert set(cfg["limits"]["train"]) == READINGS - {"window_compiles", "router_counters", "finite"}
    listed = set(manifest.metrics_of(CELL, True))
    assert listed == {
        "device_idle_pct.train", "fetch_s", "idle_attributed_pct.train", "step_ms", "pack_s",
        "input_wait_s", "attn_device_s", "head_device_s", "optimizer_device_s", "pack_fill_pct",
        "setup_trace_lower_s", "setup_backend_s", "setup_cache_misses", "ssm_device_s",
        "ssm_scan_roofline_pct", "ffn_device_s", "step_mfu_pct.granite4h",
        "gqa_core_roofline_pct.granite4h", "ssm_scan_prep_device_s", "ssm_scan_local_device_s",
        "ssm_scan_state_device_s", "ssm_scan_out_device_s"}
    assert manifest.metrics_of(CELL, False) == ["train_s", "setup_s"]


def test_the_step_count_is_the_sum_of_its_parts():
    cfg = manifest.config("seqrec-granite4h-micro-vp8")
    tokens = 8192
    shape = {"tokens": tokens, "pair_sum": tokens * 600.0, "held": []}
    scan, scan_bytes = rooflines_granite4h.ssd_scan(cfg, shape)
    assert scan == pytest.approx(3 * 9 * tokens * 64 * 4 * 64 * 128)
    given = 4096 * 2 + 2 * 128 * 2 + 64 * 4
    assert scan_bytes == pytest.approx(9 * tokens * (3 * given + 2 * 4096 * 4))
    core, core_bytes = rooflines_granite4h.gqa_core(cfg, shape)
    assert core == pytest.approx(3 * 2 * tokens * 600.0 * 32 * (64 + 64))
    assert core_bytes == pytest.approx(tokens * 2 * (2 * 32 * 64 + 2 * 8 * 64) * 2)
    dense = rooflines_granite4h.dense_parameters(cfg)
    assert dense == 9 * (2048 * 8512 + 4096 * 2048) + 10_485_760 + 10 * 50_331_648 + 12544 * 2048
    total, hbm = rooflines_granite4h.step(cfg, shape, 772_160_448)
    assert hbm == pytest.approx(772_160_448 * 28)
    assert total == pytest.approx(6 * tokens * dense + core + scan)
    # the issue's arithmetic: 37.9 TFLOP of dense products a step
    assert 37.9e12 < 6 * tokens * dense < 38.0e12


def test_the_reader_finds_nothing_without_the_mechanism():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    other = manifest.config("seqrec-lfm2-24b-a2b-ep8")
    obs = {"pio_trace": {"devices": {}}, "peaks": peaks, "seq_shape": {"config": other}}
    for params in SHARES:
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
        assert seq_model_roofline.read({"pio_trace": None}, {**NEEDS, **params}) is None
    # and on a program without the scope (the parent: no seq.ssm anywhere)
    mine = manifest.config("seqrec-granite4h-micro-vp8")
    bare = {"devices": {"/device:TPU:0": {"XLA Ops": [("a", 0.0, 1.0)], "XLA Modules": []}},
            "stacks": {"/device:TPU:0": ["jit(step)/seq.moe/dot"]}, "host": [("bench.window", 0.0, 10.0)]}
    obs = {"pio_trace": bare, "peaks": peaks,
           "seq_shape": {"config": mine, "held_by_step": [[]], "tokens": 1, "pair_sum": 1.0,
                         "steps": 1, "n_params": 1.0}}
    for params in SHARES:
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
    for scope in ("seq.ssm", "seq.ffn") + SCAN_PARTS:
        assert not seq_scope_time.read(obs, {"scope": scope})


def test_the_readers_read_a_recorded_trace_of_a_backbone_without_experts():
    """``held_by_step`` is a list of empty lists, one a step: the readers
    take it as it is."""
    cfg = manifest.config("seqrec-granite4h-micro-vp8")
    trace = {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("a", 0.0, 1.0), ("b", 1.0, 1.0), ("c", 2.0, 0.5), ("d", 2.5, 0.25),
                        ("e", 2.75, 0.125), ("f", 2.875, 0.125)],
            "XLA Modules": [("jit_step(1)", 0.0, 3.0)]}},
        "stacks": {"/device:TPU:0": [
            "jit(step)/transpose(jvp(seq.ssm))/checkpoint/seq.ssm.scan/seq.ssm.scan.local/mul",
            "jit(step)/seq.attn/seq.attn.core/while/dot", "jit(step)/seq.ssm/seq.ssm.proj/dot",
            "jit(step)/seq.ffn/dot", "jit(step)/seq.ssm/seq.ssm.scan/seq.ssm.scan.prep/cumsum",
            "jit(step)/transpose(jvp(seq.ssm))/seq.ssm.scan/seq.ssm.scan.state/dot"]},
        "host": [("bench.window", 0.0, 10.0)],
    }
    tokens = 8192
    shape = {"config": cfg, "tokens": tokens, "steps": 2, "pair_sum": tokens * 100.0,
             "held_by_step": [[], []], "n_params": 772_160_448.0}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    obs = {"pio_trace": trace, "peaks": peaks, "seq_shape": shape}
    share = seq_model_roofline.read(obs, {**NEEDS, "model": "ssd_scan", "scope": "seq.ssm.scan"})
    _, hbm = rooflines_granite4h.ssd_scan(cfg, shape)
    assert share == pytest.approx(100 * 2 * hbm / 819e9 / 1.25) and obs["bounds"]["seq.ssm.scan"] == "bytes"
    mfu = seq_model_roofline.read(obs, {**NEEDS, "model": "step", "program": "^jit_step"})
    total, _ = rooflines_granite4h.step(cfg, shape, 772_160_448.0)
    assert mfu == pytest.approx(100 * total / 197e12 / 3.0) and 0 < mfu < 100
    core = seq_model_roofline.read(obs, {**NEEDS, "model": "gqa_core", "scope": "seq.attn.core"})
    least, _ = rooflines.least_time(*(2 * n for n in rooflines_granite4h.gqa_core(cfg, shape)), peaks)
    assert core == pytest.approx(100 * least / 1.0) and 0 < core < 100
    assert seq_scope_time.read(obs, {"scope": "seq.ssm"}) == pytest.approx(1.75)
    assert seq_scope_time.read(obs, {"scope": "seq.ffn"}) == pytest.approx(0.25)
    parts = [seq_scope_time.read(obs, {"scope": scope}) for scope in SCAN_PARTS]
    assert parts[:3] == [pytest.approx(0.125), pytest.approx(1.0), pytest.approx(0.125)] and not parts[3]
