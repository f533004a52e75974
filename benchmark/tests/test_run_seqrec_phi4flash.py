"""The Phi-4-mini-flash sequence-training kind off the chip, through
``benchmark/run.py``'s own ``main`` with the rehearsal workload: sound, it
says ``correct``; with the state, Delta and the decay in bfloat16, with a
window ignored, one slot off or let across a history's first slot, with a
history allowed to see its neighbour, with a state that survives a boundary,
with a gated memory unit that reads the wrong scan, or with a step that does
not learn, it says not. And the counts behind its roofline metrics."""

import dataclasses
import json

import pytest

from benchmark import run as bench_run
from benchmark.lib import manifest, rooflines, rooflines_phi4flash
from benchmark.readers import seq_model_roofline, seq_scope_time

ARGS = ("--workload", "rehearse-train-seqrec-phi4flash", "--seconds", "1")
GROUPS = ("mamba1", "swa", "full", "cross", "gmu", "mlp", "norms", "embed")
READINGS = {
    "loss_err", "logit_err", *(f"grad_err.{g}" for g in GROUPS), "selscan_err", "swa_core_err",
    "update_err", "loss_last_over_first", "window_compiles", "router_counters", "finite"}
NEEDS = {"lib": "rooflines_phi4flash", "needs": "mamba_dt_rank"}
CELL = "train-phi4flash-long8k"
SHARES = ({"model": "step", "program": "^jit_step"},
          {"model": "selective_scan", "scope": "seq.mamba.scan"},
          {"model": "swa_core", "scope": "seq.attn.swa.core"})
NEW = {"mamba1_device_s", "mamba1_scan_roofline_pct", "gmu_device_s", "swa_core_roofline_pct",
       "step_mfu_pct.phi4flash"}


def result_of(capsys, *argv):
    assert bench_run.main(list(ARGS + argv)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def not_ok(lines):
    return {l.split()[2].rstrip(":") for l in lines if "NOT OK" in l}


def broken(capsys, monkeypatch, module, name, replacement, seed="11"):
    """One whole run with ``module.name`` replaced in the program (seed 11:
    the checked row holds four histories, one longer than the window)."""
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
    assert any(l.startswith(
        '[bench] mixers: {"cross": 1, "gmu": 1, "gqa": 1, "mamba1": 2, "swa": 1}') for l in lines)
    counters = json.loads(next(l for l in lines if l.startswith("[bench] counters: "))[18:])
    assert counters["selective_scan"] == "xla" and counters["conv"] == "xla"
    assert counters["attn_tiles_skipped_by_window"] == 3 and 0 < counters["pack_fill_pct"] <= 100


def test_traced_rehearsal_reads_the_spans_and_counters(capsys):
    result, lines = result_of(capsys, "--seed", "7", "--trace", "1")
    assert result["correct"] is True
    assert any("4 steps each" in l for l in lines)
    # (the three ``setup_*`` metrics read a process's FIRST job alone: not here)
    assert set(result["rehearsed"]) >= {
        "step_ms", "pack_s", "input_wait_s", "fetch_s", "pack_fill_pct"}
    # nothing of an expert layer or of another model's mixer is asked of this cell
    assert not [name for name in manifest.metrics_of(CELL, True)
                if any(word in name for word in ("moe", "expert", "ssm", "deltanet", "shortconv", "mla"))]


def test_control_is_not_correct_by_the_scan(capsys):
    result, lines = result_of(capsys, "--seed", "5", "--trace", "0", "--control", "bf16_state")
    assert result["correct"] is False
    # selscan_err: the reading taken from what the timed function's own scan was handed and gave
    assert "selscan_err" in not_ok(lines) and "swa_core_err" not in not_ok(lines)


@pytest.mark.parametrize("wrong", [0, 15, 17])
def test_a_window_ignored_or_one_slot_off_is_not_correct(capsys, monkeypatch, wrong):
    """The sliding layer's core run under another window than the
    configuration's 16: none, one slot shorter, one slot longer."""
    from predictionio_tpu.models import seq_backbone as bb

    real = bb.attention

    def other_window(*a, window=0, **kw):
        return real(*a, window=wrong if window else 0, **kw)

    result, lines = broken(capsys, monkeypatch, bb, "attention", other_window)
    assert result["correct"] is False and "swa_core_err" in not_ok(lines)
    assert "selscan_err" not in not_ok(lines)


def test_a_window_that_does_not_stop_at_a_history_is_not_correct(capsys, monkeypatch):
    """The sliding layer's core told that a row is one history (the window
    is right, the other layers still see the boundaries)."""
    from predictionio_tpu.models import seq_backbone as bb

    real = bb.attention

    def merged(*a, window=0, segment_ids=None, **kw):
        return real(*a, window=window, segment_ids=segment_ids * 0 + 1 if window else segment_ids, **kw)

    result, lines = broken(capsys, monkeypatch, bb, "attention", merged)
    assert result["correct"] is False and "swa_core_err" in not_ok(lines)
    assert "selscan_err" not in not_ok(lines)


def test_a_history_that_sees_its_neighbour_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.models import seq_backbone as bb

    real = bb.hidden_states

    def merged(cfg, params, tokens, seg, *args, **kwargs):
        return real(cfg, params, tokens, seg * 0 + 1, *args, **kwargs)

    result, lines = broken(capsys, monkeypatch, bb, "hidden_states", merged)
    assert result["correct"] is False and result["failed"] == result["attempted"]
    # the state, the taps and the window reached into the neighbour too
    assert {"logit_err", "grad_err.mamba1", "selscan_err", "swa_core_err"} <= not_ok(lines)


def test_a_state_that_survives_a_boundary_is_not_correct(capsys, monkeypatch):
    """The scan alone told that a row is one history (the convolution and
    attention still see the boundaries): ``selscan_err`` and the network fail."""
    from predictionio_tpu.ops import selscan

    real = selscan.selective_scan
    result, lines = broken(
        capsys, monkeypatch, selscan, "selective_scan",
        lambda x, dt, a, b, c, seg, **kw: real(x, dt, a, b, c, seg * 0 + 1, **kw))
    assert result["correct"] is False
    assert {"selscan_err", "grad_err.mamba1", "logit_err"} <= not_ok(lines)
    assert "swa_core_err" not in not_ok(lines)


def test_a_memory_unit_that_reads_the_wrong_scan_is_not_correct(capsys, monkeypatch):
    """The gated memory unit handed half the scan output: both inner checks
    pass on what they were handed, the network fails."""
    from predictionio_tpu.models import seq_backbone as bb

    real = bb.gated_memory
    result, lines = broken(capsys, monkeypatch, bb, "gated_memory",
                           lambda p, x, m, **kw: real(p, x, 0.5 * m, **kw))
    assert result["correct"] is False
    assert {"logit_err", "grad_err.gmu"} <= not_ok(lines)
    assert not {"selscan_err", "swa_core_err"} & not_ok(lines)


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
    """On a program whose backbone knows no ``mamba1`` layer the kind ends
    the run before any job, with a message."""
    from predictionio_tpu.models import seq_backbone as bb

    old = dataclasses.make_dataclass("BackboneConfig", [("hidden_size", int, 64)])
    monkeypatch.setattr(bb, "BackboneConfig", old)
    with pytest.raises(SystemExit) as stopped:
        bench_run.main(list(ARGS + ("--seed", "1", "--trace", "0")))
    assert "no Mamba-1" in str(stopped.value.code)


def test_the_cell_is_the_issues_letter_for_letter():
    cell, cfg = manifest.workload(CELL), manifest.config("seqrec-phi4-mini-flash-vp8")
    assert (cell["config"], cell["kind"], cell["traffic"], cell["chips"]) == (
        "seqrec-phi4-mini-flash-vp8", "train_seqrec_phi4flash", "long-histories-1row", 1)
    joyai = manifest.workload("train-joyai-long8k")["traffic_params"]
    assert cell["traffic_params"] == {**joyai, "rows_per_step": 1}  # that cell's generator and parameters
    assert (cell["traffic_params"]["warm_steps"], cell["traffic_params"]["trace_steps"]) == (2, 4)
    assert (cfg["algorithm"]["batch_size"], cfg["algorithm"]["seq_len"], cfg["vocab_size"]) == (1, 8192, 25008)
    assert set(cfg["control"]["train"]) == {"bf16_state"}
    assert set(cfg["limits"]["train"]) == READINGS - {"window_compiles", "router_counters", "finite"}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert cfg["layer_types"] == cfg["published"]["layer_types"][14:20]
    assert cfg["backbone"]["layer_index_offset"] == 14 and len(cfg["published"]["layer_types"]) == 32
    listed = set(manifest.metrics_of(CELL, True))
    assert listed == NEW | {
        "device_idle_pct.train", "fetch_s", "idle_attributed_pct.train", "step_ms", "pack_s",
        "input_wait_s", "attn_device_s", "ffn_device_s", "head_device_s", "optimizer_device_s",
        "pack_fill_pct", "setup_trace_lower_s", "setup_backend_s", "setup_cache_misses"}
    assert manifest.metrics_of(CELL, False) == ["train_s", "setup_s"]
    # the new metrics are this cell's alone
    for other in ("train-granite4h-packed", "train-joyai-long8k", "train-amazonbooks"):
        assert not NEW & set(manifest.metrics_of(other, True))


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of Phi-4-mini-flash-reasoning, key by key,
    but for the two keys ``reduced`` names."""
    catalog = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    cfg = manifest.config("seqrec-phi4-mini-flash-vp8")
    differs = sorted(key for key, value in catalog.items() if cfg.get(key) != value)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 200064 // 8)
    assert all(cfg["published"][key] == catalog[key] for key in differs)


def test_the_step_count_is_the_sum_of_its_parts():
    cfg = manifest.config("seqrec-phi4-mini-flash-vp8")
    tokens = 8192
    shape = {"tokens": tokens, "pair_sum": tokens * 2350.0, "swa_pair_sum": tokens * 480.0, "held": []}
    scan, scan_bytes = rooflines_phi4flash.selective_scan(cfg, shape)
    assert scan == pytest.approx(3 * 2 * tokens * 5120 * 16 * 7)
    given = 5120 * 2 + 5120 * 4 + 2 * 16 * 4
    assert scan_bytes == pytest.approx(2 * tokens * (2 * given + 2 * 5120 * 4 + 2 * 5120 * 4 + 2 * 16 * 4))
    core, core_bytes = rooflines_phi4flash.swa_core(cfg, shape)
    assert core == pytest.approx(3 * 2 * tokens * 480.0 * 40 * (64 + 128))
    assert core_bytes == pytest.approx(tokens * 2 * (40 * 64 + 20 * 64 + 10 * 128 + 20 * 128) * 2)
    full, _ = rooflines_phi4flash.full_cores(cfg, shape)
    assert full == pytest.approx(2 * 3 * 2 * tokens * 2350.0 * 40 * (64 + 128))
    dense = rooflines_phi4flash.dense_parameters(cfg)
    assert dense == (2 * (41_241_600 - 25_600 - 5_120 - 81_920 - 5_120) + 2 * 19_660_800
                     + 13_107_200 + 26_214_400 + 6 * 78_643_200 + 25008 * 2560)
    total, hbm = rooflines_phi4flash.step(cfg, shape, 697_073_792)
    assert hbm == pytest.approx(697_073_792 * 28)
    assert total == pytest.approx(6 * tokens * dense + core + full + scan)
    # the issue's arithmetic: about 34 TFLOP of dense products a step
    assert 34.0e12 < 6 * tokens * dense < 34.5e12
    # a history shorter than the window keeps all its pairs, a longer one 512 a slot
    assert rooflines_phi4flash.pairs_in_window([10], 512) == 55
    assert rooflines_phi4flash.pairs_in_window([1000], 512) == 512 * 513 / 2 + 488 * 512
    assert rooflines_phi4flash.pairs_in_window([3, 1000], 0 + 10**9) == 6 + 1000 * 1001 / 2


def test_the_reader_finds_nothing_without_the_mechanism():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    other = manifest.config("seqrec-granite4h-micro-vp8")
    obs = {"pio_trace": {"devices": {}}, "peaks": peaks, "seq_shape": {"config": other}}
    for params in SHARES:
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
        assert seq_model_roofline.read({"pio_trace": None}, {**NEEDS, **params}) is None
    # and on a program without the scope (the parent: no seq.mamba anywhere)
    mine = manifest.config("seqrec-phi4-mini-flash-vp8")
    bare = {"devices": {"/device:TPU:0": {"XLA Ops": [("a", 0.0, 1.0)], "XLA Modules": []}},
            "stacks": {"/device:TPU:0": ["jit(step)/seq.attn/seq.attn.core/dot"]},
            "host": [("bench.window", 0.0, 10.0)]}
    obs = {"pio_trace": bare, "peaks": peaks,
           "seq_shape": {"config": mine, "held_by_step": [[]], "tokens": 1, "pair_sum": 1.0,
                         "swa_pair_sum": 1.0, "steps": 1, "n_params": 1.0}}
    for params in SHARES:
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
    for scope in ("seq.mamba", "seq.gmu", "seq.attn.swa.core"):
        assert not seq_scope_time.read(obs, {"scope": scope})


def test_the_readers_read_a_recorded_trace_and_tell_the_sliding_core_from_the_others():
    cfg = manifest.config("seqrec-phi4-mini-flash-vp8")
    trace = {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("a", 0.0, 1.0), ("b", 1.0, 1.0), ("c", 2.0, 0.5), ("d", 2.5, 0.25),
                        ("e", 2.75, 0.125), ("f", 2.875, 0.125)],
            "XLA Modules": [("jit_step(1)", 0.0, 3.0)]}},
        "stacks": {"/device:TPU:0": [
            "jit(step)/transpose(jvp(seq.mamba))/checkpoint/seq.mamba.scan/while/mul",
            "jit(step)/seq.attn/seq.attn.core/while/dot", "jit(step)/seq.mamba/seq.mamba.proj/dot",
            "jit(step)/seq.gmu/dot", "jit(step)/seq.attn/seq.attn.swa/seq.attn.swa.core/while/dot",
            "jit(step)/transpose(jvp(seq.attn))/seq.attn.swa/dot"]},
        "host": [("bench.window", 0.0, 10.0)],
    }
    tokens = 8192
    shape = {"config": cfg, "tokens": tokens, "steps": 2, "pair_sum": tokens * 2350.0,
             "swa_pair_sum": tokens * 480.0, "held_by_step": [[], []], "n_params": 697_073_792.0}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    obs = {"pio_trace": trace, "peaks": peaks, "seq_shape": shape}
    share = seq_model_roofline.read(obs, {**NEEDS, "model": "selective_scan", "scope": "seq.mamba.scan"})
    _, hbm = rooflines_phi4flash.selective_scan(cfg, shape)
    assert share == pytest.approx(100 * 2 * hbm / 819e9 / 1.0) and obs["bounds"]["seq.mamba.scan"] == "bytes"
    mfu = seq_model_roofline.read(obs, {**NEEDS, "model": "step", "program": "^jit_step"})
    total, _ = rooflines_phi4flash.step(cfg, shape, 697_073_792.0)
    assert mfu == pytest.approx(100 * total / 197e12 / 3.0) and 0 < mfu < 100
    core = seq_model_roofline.read(obs, {**NEEDS, "model": "swa_core", "scope": "seq.attn.swa.core"})
    least, _ = rooflines.least_time(*(2 * n for n in rooflines_phi4flash.swa_core(cfg, shape)), peaks)
    # the sliding layer's core alone: 0.125 s, not the full layer's second beside it
    assert core == pytest.approx(100 * least / 0.125) and 0 < core < 100
    assert seq_scope_time.read(obs, {"scope": "seq.mamba"}) == pytest.approx(1.5)
    assert seq_scope_time.read(obs, {"scope": "seq.gmu"}) == pytest.approx(0.25)
    assert seq_scope_time.read(obs, {"scope": "seq.attn"}) == pytest.approx(1.25)
    assert seq_scope_time.read(obs, {"scope": "seq.attn.core"}) == pytest.approx(1.0)
