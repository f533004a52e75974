"""The sequence-training kind off the chip, through ``benchmark/run.py``'s
own ``main`` with the rehearsal workload: sound, it says ``correct``; with
the timed path broken underneath (a history allowed to see its neighbour),
or with the delta rule's state and gates in bfloat16, it says not."""

import json

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import rooflines_seq, seq_scopes, synth_seq

ARGS = ("--workload", "rehearse-train-seqrec", "--seconds", "1")


def result_of(capsys, *argv):
    assert bench_run.main(list(ARGS + argv)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def test_rehearsal_is_correct_and_prints_every_number(capsys):
    result, lines = result_of(capsys, "--seed", "3000000019", "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and result["metrics"] == {}
    assert set(result["rehearsed"]) == {"train_s", "setup_s"}
    compared = {l.split()[2].rstrip(":") for l in lines if l.startswith("[bench] compared ")}
    assert compared >= {
        "loss_err", "logit_err", "grad_err.deltanet", "grad_err.attention", "grad_err.router",
        "grad_err.experts", "grad_err.shared", "grad_err.embed", "grad_err.head",
        "delta_rule_err", "update_err",
        "loss_last_over_first", "window_compiles", "dropped", "finite"}


def test_traced_rehearsal_reads_the_spans_and_counters(capsys):
    """The traced job is cut to ``trace_steps`` (4 of 12): too few for the
    loss to fall, so ``correct`` comes from the whole job that warmed up."""
    result, lines = result_of(capsys, "--seed", "7", "--trace", "1")
    assert result["correct"] is True
    assert any("4 steps each" in l for l in lines)
    assert sum(l.startswith("[bench] loss by step:") and len(l.split()) == 4 + 12 for l in lines) == 1
    assert set(result["rehearsed"]) >= {
        "step_ms", "pack_s", "input_wait_s", "fetch_s", "pack_fill_pct",
        "expert_load_max_over_mean"}


def test_a_history_that_sees_its_neighbour_is_not_correct(capsys, monkeypatch):
    """The timed path broken once: every slot of a row is taken for one
    history, so state, convolution and attention run across boundaries."""
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    real = bb.hidden_states

    def merged(cfg, params, tokens, seg, *args, **kwargs):
        return real(cfg, params, tokens, seg * 0 + 1, *args, **kwargs)

    monkeypatch.setattr(bb, "hidden_states", merged)
    sequencerec._programs.cache_clear()  # programs traced before the break
    try:
        result, _ = result_of(capsys, "--seed", "5", "--trace", "0")
    finally:
        sequencerec._programs.cache_clear()
    assert result["correct"] is False and result["failed"] == result["attempted"]


def test_control_is_not_correct(capsys):
    result, lines = result_of(capsys, "--seed", "5", "--trace", "0", "--control", "bf16_state")
    assert result["correct"] is False
    assert any("NOT OK" in l and "grad_err.deltanet" in l for l in lines)
    # the reading taken from what the timed function's own scan ran on and gave
    assert any("NOT OK" in l and "delta_rule_err" in l for l in lines)


@pytest.mark.parametrize("fault", ["no_bias_correction", "ascent"])
def test_a_wrong_optimizer_step_is_not_correct(capsys, monkeypatch, fault):
    """The step's optimizer broken underneath: another rule for the
    step's length (RMS scaling and momentum whose moments are not corrected
    for their start), or a step up the gradient. Loss and gradients of the
    final parameters still match the reference; the step does not."""
    import optax

    from predictionio_tpu.models import sequencerec

    sound = optax.adamw

    def broken(learning_rate):
        if fault == "ascent":
            return sound(-learning_rate)
        return optax.chain(
            optax.scale_by_rms(decay=0.999, eps=1e-8, initial_scale=0.0),
            optax.trace(decay=0.9, nesterov=False), optax.scale(-0.1 * learning_rate))

    monkeypatch.setattr(optax, "adamw", broken)
    sequencerec._programs.cache_clear()
    try:
        result, lines = result_of(capsys, "--seed", "5", "--trace", "0")
    finally:
        sequencerec._programs.cache_clear()
    assert result["correct"] is False
    bad = {l.split()[2].rstrip(":") for l in lines if "NOT OK" in l}
    assert "update_err" in bad and not any(b.startswith("grad_err") for b in bad)


def test_histories_follow_their_law():
    traffic = {"length_min": 16, "length_exponent": 1.1, "length_cap": 8192,
               "item_exponent": 1.0, "follow_probability": 0.5}
    a = synth_seq.histories(traffic, 18992, 400_000, 2147483659)
    b = synth_seq.histories(traffic, 18992, 400_000, 2147483659)
    assert all((x == y).all() for x, y in zip(a, b))
    lengths = np.array([len(h) for h in a])
    assert lengths.min() >= 16 and lengths.max() <= 8192 and 400_000 <= lengths.sum() < 410_000
    assert 50 < lengths.mean() < 150
    ids = np.concatenate(a)
    assert ids.min() >= 0 and ids.max() < 18992
    # half the steps follow the seeded successor: the commonest successor
    # of a common item follows it about half the time
    top = np.bincount(ids).argmax()
    after = ids[1:][ids[:-1] == top]
    assert 0.35 < np.bincount(after).max() / len(after) < 0.7


def test_scope_names_are_found_inside_transformed_stacks():
    stack = ("jit(step)/jit(main)/transpose(jvp(seq.deltanet))/checkpoint/rematted_computation/"
             "seq.deltanet.scan/while/body/dot_general")
    assert seq_scopes.names_in(stack) == {"seq.deltanet", "seq.deltanet.scan"}
    trace = {
        "devices": {"/device:TPU:0": {"XLA Ops": [
            ("a", 0.0, 1.0), ("b", 1.0, 2.0), ("c", 2.5, 0.5)], "XLA Modules": []}},
        "stacks": {"/device:TPU:0": [stack, "jit(step)/seq.moe/seq.moe.experts/x", "jit(step)/other"]},
        "host": [("bench.window", 0.0, 10.0)],
    }
    assert seq_scopes.scope_seconds(trace, "seq.deltanet") == pytest.approx(1.0)
    assert seq_scopes.scope_seconds(trace, "seq.moe") == pytest.approx(2.0)
    assert seq_scopes.scope_seconds(trace, "seq.attn") is None


def test_the_step_count_is_the_sum_of_its_parts():
    from benchmark.lib import manifest

    cfg = manifest.config("seqrec-qwen3next-80b-a3b-ep16")
    shape = {"tokens": 16384, "pair_sum": 16384 * 100.0, "held": [10240.0] * 4}
    scan, _ = rooflines_seq.deltanet_scan(cfg, shape)
    experts, _ = rooflines_seq.moe_experts(cfg, shape)
    total, hbm = rooflines_seq.step(cfg, shape, 625_667_136)
    assert scan == pytest.approx(3 * 3 * 16384 * 32 * 6 * 128 * 128)
    assert experts == pytest.approx(4 * 3 * 10240 * 6 * 2048 * 512)
    assert total > scan + experts and hbm == pytest.approx(625_667_136 * 28)
