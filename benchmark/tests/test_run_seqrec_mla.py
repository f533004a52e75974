"""The latent-attention sequence-training kind off the chip, through
``benchmark/run.py``'s own ``main`` with the rehearsal workload: sound, it
says ``correct``; with the softmax statistics in bfloat16, with a history
allowed to see its neighbour, with the bias stepped the wrong way, or with a
held router moved, it
says not. And the counts behind its roofline metrics."""

import dataclasses
import json

import pytest

from benchmark import run as bench_run
from benchmark.lib import manifest, rooflines_mla
from benchmark.readers import seq_mla_roofline

ARGS = ("--workload", "rehearse-train-seqrec-mla", "--seconds", "1")
READINGS = {
    "loss_err", "mtp_loss_err", "logit_err", "mtp_logit_err", "grad_err.latent", "grad_err.router",
    "grad_err.experts", "grad_err.shared", "grad_err.dense", "grad_err.norms", "grad_err.embed",
    "grad_err.head", "grad_err.mtp", "attn_core_err", "update_err", "bias_err",
    "router_moved", "loss_last_over_first", "window_compiles", "dropped", "finite"}


def result_of(capsys, *argv):
    assert bench_run.main(list(ARGS + argv)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def not_ok(lines):
    return {l.split()[2].rstrip(":") for l in lines if "NOT OK" in l}


def test_rehearsal_is_correct_and_prints_every_number(capsys):
    result, lines = result_of(capsys, "--seed", "3000000019", "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and result["metrics"] == {}
    assert set(result["rehearsed"]) == {"train_s", "setup_s"}
    compared = {l.split()[2].rstrip(":") for l in lines if l.startswith("[bench] compared ")}
    assert compared == READINGS
    assert any(l.startswith("[bench] the module's loss by step:") for l in lines)


def test_traced_rehearsal_reads_the_spans_and_counters(capsys):
    result, lines = result_of(capsys, "--seed", "7", "--trace", "1")
    assert result["correct"] is True
    assert any("4 steps each" in l for l in lines)
    assert set(result["rehearsed"]) >= {
        "step_ms", "pack_s", "input_wait_s", "fetch_s", "pack_fill_pct",
        "expert_load_max_over_mean"}
    counters = json.loads(next(l for l in lines if l.startswith("[bench] counters: "))[18:])
    assert counters["router_tokens_mean"] == 2 * 64 * 3 / 8  # slots x experts a token / width
    assert 0 < counters["router_bias_abs_max"] <= 4 * 0.001 + 1e-9


def test_control_is_not_correct(capsys):
    result, lines = result_of(capsys, "--seed", "5", "--trace", "0", "--control", "bf16_softmax_state")
    assert result["correct"] is False
    # the reading taken from what the timed function's own attention was handed and gave
    assert "attn_core_err" in not_ok(lines)


def test_a_history_that_sees_its_neighbour_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    real = bb.hidden_states

    def merged(cfg, params, tokens, seg, *args, **kwargs):
        return real(cfg, params, tokens, seg * 0 + 1, *args, **kwargs)

    monkeypatch.setattr(bb, "hidden_states", merged)
    sequencerec._programs.cache_clear()  # programs traced before the break
    try:
        result, lines = result_of(capsys, "--seed", "5", "--trace", "0")
    finally:
        sequencerec._programs.cache_clear()
    assert result["correct"] is False and result["failed"] == result["attempted"]
    assert {"logit_err", "grad_err.latent"} <= not_ok(lines)


def test_a_bias_stepped_the_wrong_way_is_not_correct(capsys, monkeypatch):
    """The rule turned round (the busy expert's bias goes up): gradients
    and the optimizer's step still match, the bias step does not."""
    import jax.numpy as jnp

    from predictionio_tpu.models import sequencerec

    real = jnp.sign
    monkeypatch.setattr(jnp, "sign", lambda x: -real(x))
    sequencerec._programs.cache_clear()
    try:
        result, lines = result_of(capsys, "--seed", "5", "--trace", "0")
    finally:
        sequencerec._programs.cache_clear()
    assert result["correct"] is False and not_ok(lines) == {"bias_err"}


def test_a_held_router_that_moves_is_not_correct(capsys, monkeypatch):
    """The configuration holds its routers (``router_trains`` off); a step
    that lets the optimizer move them all the same is seen, and by that
    reading alone."""
    from predictionio_tpu.models import seq_backbone, sequencerec

    real = seq_backbone.step_routers
    monkeypatch.setattr(
        seq_backbone, "step_routers",
        lambda cfg, *rest: real(dataclasses.replace(cfg, router_trains=True), *rest))
    sequencerec._programs.cache_clear()
    try:
        result, lines = result_of(capsys, "--seed", "6", "--trace", "0")
    finally:
        sequencerec._programs.cache_clear()
    assert result["correct"] is False and not_ok(lines) == {"router_moved"}


def test_the_parent_program_is_refused_at_once(capsys, monkeypatch):
    """On a program whose backbone knows no latent attention the kind ends
    the run before any job: it would train another model."""
    import dataclasses

    from predictionio_tpu.models import seq_backbone as bb

    old = dataclasses.make_dataclass("BackboneConfig", [("hidden_size", int, 64)])
    monkeypatch.setattr(bb, "BackboneConfig", old)
    with pytest.raises(SystemExit) as stopped:
        bench_run.main(list(ARGS + ("--seed", "1", "--trace", "0")))
    assert stopped.value.code not in (0, None)


def test_the_step_count_is_the_sum_of_its_parts():
    cfg = manifest.config("seqrec-joyai-flash-48b-a3b-ep16")
    tokens = 16384
    # every expert layer at even routing: 8 of 256 held 16 ways
    shape = {"tokens": tokens, "pair_sum": tokens * 2350.0, "held": [tokens * 8 * 16 / 256.0] * 5}
    core, core_bytes = rooflines_mla.mla_core(cfg, shape)
    assert core == pytest.approx(6 * 3 * 2 * tokens * 2350.0 * 32 * (192 + 128))
    assert core_bytes == pytest.approx(6 * tokens * 32 * 2 * (2 * 192 + 2 * 128) * 2)
    total, hbm = rooflines_mla.step(cfg, shape, 680_441_088)
    assert hbm == pytest.approx(680_441_088 * 28)
    # the issue's own arithmetic: 918 M operations a token forward, 45 TFLOP a step
    per_token = total / 3 / tokens
    assert 900e6 < per_token < 935e6 and 44e12 < total < 46.5e12
    mixer = 2 * 26_347_520 - 2 * (1536 + 512)  # the mixer's matrices without its two norms
    dense = (6 * mixer + 2 * 3 * 2048 * 7168 + 5 * 2 * (2048 * 256 + 3 * 2048 * 768)
             + 2 * 2 * 2048 * 2048 + 2 * 2 * 16160 * 2048)
    experts = 5 * (8 * 16 / 256) * 2 * 3 * 2048 * 768
    assert per_token == pytest.approx(dense + core / 3 / tokens + experts)


def test_the_reader_finds_nothing_without_the_mechanism():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    other = manifest.config("seqrec-qwen3next-80b-a3b-ep16")
    obs = {"pio_trace": {"devices": {}}, "peaks": peaks, "seq_shape": {"config": other}}
    for params in ({"model": "step", "program": "^jit_step"},
                   {"model": "mla_core", "scope": "seq.attn.core"}):
        assert seq_mla_roofline.read(obs, params) is None
        assert seq_mla_roofline.read({"pio_trace": None}, params) is None


def test_the_readers_read_a_recorded_trace():
    cfg = manifest.config("seqrec-joyai-flash-48b-a3b-ep16")
    stack = "jit(step)/transpose(jvp(seq.attn))/checkpoint/seq.attn.core/pallas_call"
    trace = {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("a", 0.0, 1.0), ("b", 1.0, 1.0)],
            "XLA Modules": [("jit_step(1)", 0.0, 2.0)]}},
        "stacks": {"/device:TPU:0": [stack, "jit(step)/seq.mtp/seq.attn/seq.attn.latent/dot"]},
        "host": [("bench.window", 0.0, 10.0)],
    }
    tokens = 16384
    shape = {"config": cfg, "tokens": tokens, "steps": 1, "pair_sum": tokens * 2350.0,
             "held_by_step": [[8192.0] * 5], "n_params": 680_441_088.0}
    obs = {"pio_trace": trace, "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "seq_shape": shape}
    core = seq_mla_roofline.read(obs, {"model": "mla_core", "scope": "seq.attn.core"})
    flops, _ = rooflines_mla.mla_core(cfg, shape)
    assert core == pytest.approx(100 * flops / 197e12 / 1.0) and obs["bounds"]["seq.attn.core"] == "flops"
    mfu = seq_mla_roofline.read(obs, {"model": "step", "program": "^jit_step"})
    total, _ = rooflines_mla.step(cfg, {**shape, "held": [8192.0] * 5}, 680_441_088.0)
    assert mfu == pytest.approx(100 * total / 197e12 / 2.0)
