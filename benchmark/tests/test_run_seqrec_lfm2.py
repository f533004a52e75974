"""The LFM2 sequence-training kind off the chip, through
``benchmark/run.py``'s own ``main`` with the rehearsal workload: sound, it
says ``correct``; with the convolution's gates and taps in bfloat16, with a
history allowed to see its neighbour, with the bias stepped the wrong way,
or with a held router moved, it says not. And the counts behind its
roofline metrics."""

import dataclasses
import json

import pytest

from benchmark import run as bench_run
from benchmark.lib import manifest, rooflines_lfm2
from benchmark.readers import seq_model_roofline

ARGS = ("--workload", "rehearse-train-seqrec-lfm2", "--seconds", "1")
READINGS = {
    "loss_err", "logit_err", "grad_err.shortconv", "grad_err.attention", "grad_err.dense",
    "grad_err.router", "grad_err.experts", "grad_err.norms", "grad_err.embed", "shortconv_err",
    "update_err", "bias_err", "router_moved", "loss_last_over_first", "window_compiles",
    "dropped", "finite"}
NEEDS = {"lib": "rooflines_lfm2", "needs": "conv_L_cache"}


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
    assert any(l.startswith('[bench] mixers: {"gqa": 1, "shortconv": 4}') for l in lines)


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
    result, lines = result_of(capsys, "--seed", "5", "--trace", "0", "--control", "bf16_conv_gates")
    assert result["correct"] is False
    # the reading taken from what the timed function's own chain was handed and gave
    assert "shortconv_err" in not_ok(lines)


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
    # the taps reached into the neighbour too: the chain itself is another
    assert {"logit_err", "grad_err.shortconv", "shortconv_err"} <= not_ok(lines)


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
    """On a program whose backbone takes no pattern from ``layer_types``
    the kind ends the run before any job: it would train another model."""
    from predictionio_tpu.models import seq_backbone as bb

    old = dataclasses.make_dataclass("BackboneConfig", [("hidden_size", int, 64)])
    monkeypatch.setattr(bb, "BackboneConfig", old)
    with pytest.raises(SystemExit) as stopped:
        bench_run.main(list(ARGS + ("--seed", "1", "--trace", "0")))
    assert stopped.value.code not in (0, None)


def test_the_step_count_is_the_sum_of_its_parts():
    cfg = manifest.config("seqrec-lfm2-24b-a2b-ep8")
    tokens = 16384
    # every sparse layer at even routing: 4 of 64 held 8 ways
    shape = {"tokens": tokens, "pair_sum": tokens * 600.0, "held": [tokens * 4 * 8 / 64.0] * 4}
    chain, chain_bytes = rooflines_lfm2.shortconv_chain(cfg, shape)
    assert chain == pytest.approx(3 * 4 * tokens * 2048 * 7)
    assert chain_bytes == pytest.approx(4 * tokens * 2048 * 12 * 2)
    core, core_bytes = rooflines_lfm2.gqa_core(cfg, shape)
    assert core == pytest.approx(3 * 2 * tokens * 600.0 * 32 * (64 + 64))
    assert core_bytes == pytest.approx(tokens * 2 * (2 * 32 * 64 + 2 * 8 * 64) * 2)
    total, hbm = rooflines_lfm2.step(cfg, shape, 469_285_248)
    assert hbm == pytest.approx(469_285_248 * 28)
    dense = (4 * 16_777_216 + 10_485_760 + 72_351_744 + 4 * 131_072 + 8192 * 2048)
    experts = 4 * (4 * 8 / 64) * 3 * 2048 * 1536
    per_token = total / 3 / tokens
    assert per_token == pytest.approx(2 * (dense + experts) + (core + chain) / 3 / tokens)
    # the issue's arithmetic: 167 M parameters of dense products a token, 18 TFLOP a step
    assert 166e6 < dense < 168e6 and 18e12 < total < 20e12


def test_the_reader_finds_nothing_without_the_mechanism():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    other = manifest.config("seqrec-joyai-flash-48b-a3b-ep16")
    obs = {"pio_trace": {"devices": {}}, "peaks": peaks, "seq_shape": {"config": other}}
    for params in ({"model": "step", "program": "^jit_step"},
                   {"model": "shortconv_chain", "scope": "seq.shortconv.conv"},
                   {"model": "gqa_core", "scope": "seq.attn.core"}):
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
        assert seq_model_roofline.read({"pio_trace": None}, {**NEEDS, **params}) is None
    # and on a program without the scope (the parent: no seq.shortconv anywhere)
    mine = manifest.config("seqrec-lfm2-24b-a2b-ep8")
    bare = {"devices": {"/device:TPU:0": {"XLA Ops": [("a", 0.0, 1.0)], "XLA Modules": []}},
            "stacks": {"/device:TPU:0": ["jit(step)/seq.attn/dot"]}, "host": [("bench.window", 0.0, 10.0)]}
    obs = {"pio_trace": bare, "peaks": peaks,
           "seq_shape": {"config": mine, "held_by_step": [[1.0] * 4], "tokens": 1, "pair_sum": 1.0,
                         "steps": 1, "n_params": 1.0}}
    for params in ({"model": "step", "program": "^jit_step"},
                   {"model": "shortconv_chain", "scope": "seq.shortconv.conv"}):
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None


def test_the_readers_read_a_recorded_trace():
    cfg = manifest.config("seqrec-lfm2-24b-a2b-ep8")
    trace = {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("a", 0.0, 1.0), ("b", 1.0, 1.0), ("c", 2.0, 0.5)],
            "XLA Modules": [("jit_step(1)", 0.0, 2.5)]}},
        "stacks": {"/device:TPU:0": [
            "jit(step)/transpose(jvp(seq.shortconv))/checkpoint/seq.shortconv.conv/mul",
            "jit(step)/seq.attn/seq.attn.core/while/dot", "jit(step)/seq.shortconv/seq.shortconv.proj/dot"]},
        "host": [("bench.window", 0.0, 10.0)],
    }
    tokens = 16384
    shape = {"config": cfg, "tokens": tokens, "steps": 1, "pair_sum": tokens * 600.0,
             "held_by_step": [[8192.0] * 4], "n_params": 469_285_248.0}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    obs = {"pio_trace": trace, "peaks": peaks, "seq_shape": shape}
    chain = seq_model_roofline.read(
        obs, {**NEEDS, "model": "shortconv_chain", "scope": "seq.shortconv.conv"})
    _, hbm = rooflines_lfm2.shortconv_chain(cfg, shape)
    assert chain == pytest.approx(100 * hbm / 819e9 / 1.0) and obs["bounds"]["seq.shortconv.conv"] == "bytes"
    core = seq_model_roofline.read(obs, {**NEEDS, "model": "gqa_core", "scope": "seq.attn.core"})
    flops, _ = rooflines_lfm2.gqa_core(cfg, shape)
    assert core == pytest.approx(100 * flops / 197e12 / 1.0)
    mfu = seq_model_roofline.read(obs, {**NEEDS, "model": "step", "program": "^jit_step"})
    total, _ = rooflines_lfm2.step(cfg, {**shape, "held": [8192.0] * 4}, 469_285_248.0)
    assert mfu == pytest.approx(100 * total / 197e12 / 2.5)
    from benchmark.readers import seq_scope_time
    assert seq_scope_time.read(obs, {"scope": "seq.shortconv"}) == pytest.approx(1.5)
