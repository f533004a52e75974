"""The Nemotron-H sequence-training kind off the chip, through
``benchmark/run.py``'s own ``main`` with the rehearsal workload: sound, it
says ``correct``; with the state, Delta and the decay's running sums in
bfloat16, with every head on the first group's B and C, with the square left
out of an expert, with a history allowed to see its neighbour, or with a step
that does not learn, it says not. And the counts behind its roofline metrics."""

import dataclasses
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.lib import manifest, rooflines, rooflines_nemotronh
from benchmark.readers import seq_model_roofline, seq_products_roofline, seq_scope_time

ARGS = ("--workload", "rehearse-train-seqrec-nemotronh", "--seconds", "1")
READINGS = {
    "loss_err", "logit_err", "grad_err.ssm", "grad_err.attention", "grad_err.router",
    "grad_err.experts", "grad_err.shared", "grad_err.norms", "grad_err.embed", "grad_err.head",
    "ssd_err", "moe_err", "update_err", "bias_err", "router_moved", "loss_last_over_first",
    "window_compiles", "dropped", "finite"}
NEEDS = {"lib": "rooflines_nemotronh", "needs": "hybrid_override_pattern"}
CELL, CONFIG = "train-nemotron3nano-packed8k", "seqrec-nemotron3-nano-30b-a3b-ep16"
SHARES = ({"model": "step", "program": "^jit_step"}, {"model": "ssd_scan", "scope": "seq.ssm.scan"})
PRODUCTS = {**NEEDS, "model": "moe_experts", "scope": "seq.moe.experts", "outer": "seq.moe",
            "events": "ragged-dot"}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def result_of(capsys, *argv):
    assert bench_run.main(list(ARGS + argv)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def not_ok(lines):
    return {l.split()[2].rstrip(":") for l in lines if "NOT OK" in l}


def broken(capsys, monkeypatch, module, name, replacement, seed="5"):
    """One whole run with ``module.name`` (a dict: its entry ``name``) replaced
    in the program."""
    from predictionio_tpu.models import sequencerec
    from predictionio_tpu.ops import moe

    if isinstance(module, dict):
        monkeypatch.setitem(module, name, replacement)
    else:
        monkeypatch.setattr(module, name, replacement)
    kept = (moe._one_pass, moe._pull_pass)
    for fn in kept:
        fn.clear_cache()
    sequencerec._programs.cache_clear()  # programs traced before the break
    try:
        return result_of(capsys, "--seed", seed, "--trace", "0")
    finally:
        sequencerec._programs.cache_clear()
        for fn in kept:
            fn.clear_cache()


def test_rehearsal_is_correct_and_prints_every_number(capsys):
    result, lines = result_of(capsys, "--seed", "3000000019", "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and result["metrics"] == {}
    assert set(result["rehearsed"]) == {"train_s", "setup_s"}
    compared = {l.split()[2].rstrip(":") for l in lines if l.startswith("[bench] compared ")}
    assert compared == READINGS
    assert any(l.startswith('[bench] mixers: {"gqa": 1, "mamba2": 4, "moe": 4}') for l in lines)
    counters = json.loads(next(l for l in lines if l.startswith("[bench] counters: "))[18:])
    assert (counters["ssd_scan"], counters["ssd_groups"], counters["expert_act"]) == ("xla", 2, "relu2")
    assert counters["dropped"] == 0 and counters["passes_most"] >= 1
    assert 0 < counters["pack_fill_pct"] <= 100 and counters["router_bias_abs_max"] > 0


def test_traced_rehearsal_reads_the_spans_and_counters(capsys):
    result, lines = result_of(capsys, "--seed", "7", "--trace", "1")
    assert result["correct"] is True
    assert any("4 steps each" in l for l in lines)
    # (the three ``setup_*`` metrics read a process's FIRST job alone: not always here)
    assert set(result["rehearsed"]) >= {
        "step_ms", "pack_s", "input_wait_s", "fetch_s", "pack_fill_pct", "expert_load_max_over_mean"}


def test_control_is_not_correct_by_the_scan(capsys):
    result, lines = result_of(capsys, "--seed", "5", "--trace", "0", "--control", "bf16_state")
    assert result["correct"] is False
    # ssd_err: the reading taken from what the timed function's own scan was handed and gave;
    # the expert layer computes as in the sound build
    assert "ssd_err" in not_ok(lines) and "moe_err" not in not_ok(lines)


def test_heads_that_all_read_the_first_groups_b_and_c_are_not_correct(capsys, monkeypatch):
    """The timed path broken where this backbone differs from the fourth's:
    the scan handed group 0's B and C for every head (one group's rule)."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import ssd

    real = ssd.ssd_scan

    def one_group(u, dt, a, b, c, seg, *, groups, **kw):
        n = b.shape[-1] // groups
        first = lambda t: jnp.tile(t[..., :n], (1, 1, groups))  # noqa: E731
        return real(u, dt, a, first(b), first(c), seg, groups=groups, **kw)

    result, lines = broken(capsys, monkeypatch, ssd, "ssd_scan", one_group)
    assert result["correct"] is False and result["failed"] == result["attempted"]
    assert {"ssd_err", "grad_err.ssm", "logit_err"} <= not_ok(lines)


def test_an_expert_without_its_square_is_not_correct(capsys, monkeypatch):
    """``W_down relu(W_up h)`` for the shared expert: the expert layer's own
    reading fails, and the network with it."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import moe

    def relu1(w, x, cd):
        up = jnp.dot(x.astype(cd), w["wu"].astype(cd), preferred_element_type=jnp.float32)
        return jnp.dot(jax.nn.relu(up).astype(cd), w["wd"].astype(cd),
                       preferred_element_type=jnp.float32)

    result, lines = broken(capsys, monkeypatch, moe.ACTS, "relu2", relu1)
    assert result["correct"] is False
    assert {"moe_err", "grad_err.shared", "logit_err"} <= not_ok(lines) and "ssd_err" not in not_ok(lines)


def test_a_history_that_sees_its_neighbour_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.models import seq_backbone as bb

    real = bb.hidden_states

    def merged(cfg, params, tokens, seg, *args, **kwargs):
        return real(cfg, params, tokens, seg * 0 + 1, *args, **kwargs)

    result, lines = broken(capsys, monkeypatch, bb, "hidden_states", merged)
    assert result["correct"] is False and result["failed"] == result["attempted"]
    # the state and the taps reached into the neighbour too: the scan itself is another
    assert {"logit_err", "grad_err.attention", "ssd_err"} <= not_ok(lines)


def test_a_step_that_does_not_learn_is_not_correct(capsys, monkeypatch):
    import optax

    real = optax.adamw
    result, lines = broken(capsys, monkeypatch, optax, "adamw", lambda rate: real(0.0))
    assert result["correct"] is False
    assert {"loss_last_over_first", "update_err"} <= not_ok(lines)


def test_the_parent_program_is_refused_at_once(capsys, monkeypatch):
    """On a program whose backbone knows no layer of one part the kind ends
    the run before any job, with a message."""
    from predictionio_tpu.models import seq_backbone as bb

    old = dataclasses.make_dataclass("BackboneConfig", [("hidden_size", int, 64)])
    monkeypatch.setattr(bb, "BackboneConfig", old)
    with pytest.raises(SystemExit) as stopped:
        bench_run.main(list(ARGS + ("--seed", "1", "--trace", "0")))
    assert "no layer of one part" in str(stopped.value.code)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(manifest.REPO, "predictionio_tpu", "testing", "nemotronh_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(manifest.ROOT, "lib", "reference_nemotronh.py")) as f:
        assert f.read() == ours


def test_the_cell_is_the_issues_letter_for_letter():
    cell, cfg = manifest.workload(CELL), manifest.config(CONFIG)
    assert (cell["config"], cell["kind"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_seqrec_nemotronh", "packed-histories-8k", 1)
    # the first sequence cell's generator and parameters, as the third's
    assert cell["traffic_params"] == manifest.workload("train-lfm2-packed8k")["traffic_params"]
    assert cell["traffic_params"] == manifest.workload("train-qwen3next-packed8k")["traffic_params"]
    assert (cell["traffic_params"]["rows_per_step"], cell["traffic_params"]["warm_steps"],
            cell["traffic_params"]["trace_steps"]) == (2, 2, 4)
    assert "768 tokens a held expert" in cell["why"] and "mixers see 16x" in cell["why"]
    assert len(cell["why"]) <= 200
    assert (cfg["algorithm"]["batch_size"], cfg["algorithm"]["seq_len"], cfg["vocab_size"]) == (2, 8192, 16384)
    assert 2 * 8192 * cfg["num_experts_per_tok"] / cfg["router_width"] == 768
    assert set(cfg["control"]["train"]) == {"bf16_state"}
    assert set(cfg["limits"]["train"]) == READINGS - {"window_compiles", "dropped", "finite", "router_moved"}
    for key in ("source", "reduced", "published", "deployment", "assumed", "bytes", "precision",
                "limits_note"):
        assert cfg[key], key
    listed = set(manifest.metrics_of(CELL, True))
    assert listed == {
        "device_idle_pct.train", "idle_attributed_pct.train", "fetch_s", "step_ms", "pack_s",
        "input_wait_s", "pack_fill_pct", "expert_load_max_over_mean", "ssm_device_s",
        "ssm_scan_prep_device_s", "moe_device_s", "attn_device_s", "head_device_s",
        "optimizer_device_s", "setup_trace_lower_s", "setup_backend_s", "setup_cache_misses",
        "step_mfu_pct.nemotronh", "ssm_scan_roofline_pct.nemotronh",
        "moe_experts_roofline_pct.nemotronh", "moe_shared_device_s"}
    assert manifest.metrics_of(CELL, False) == ["train_s", "setup_s"]
    # the accepted counts are another model's: one group, three matrices an expert
    assert not listed & {"ssm_scan_roofline_pct", "moe_experts_roofline_pct", "step_mfu_pct",
                         "gqa_core_roofline_pct", "index_sort_s", "ssm_scan_local_device_s"}
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG


def test_the_step_count_is_the_sum_of_its_parts():
    cfg = manifest.config(CONFIG)
    tokens = 16384
    held = [6144.0] * 4  # 768 tokens each of 8 held experts, four expert layers
    shape = {"tokens": tokens, "pair_sum": tokens * 700.0, "held": held}
    scan, scan_bytes = rooflines_nemotronh.ssd_scan(cfg, shape)
    assert scan == pytest.approx(3 * 4 * tokens * 64 * 4 * 64 * 128)
    given = 4096 * 2 + 2 * 8 * 128 * 2 + 64 * 4  # eight groups' B and C
    assert scan_bytes == pytest.approx(4 * tokens * (3 * given + 2 * 4096 * 4))
    core, core_bytes = rooflines_nemotronh.gqa_core(cfg, shape)
    assert core == pytest.approx(3 * 2 * tokens * 700.0 * 32 * (128 + 128))
    assert core_bytes == pytest.approx(tokens * 2 * (2 * 32 * 128 + 2 * 2 * 128) * 2)
    experts, expert_bytes = rooflines_nemotronh.moe_experts(cfg, shape)
    assert experts == pytest.approx(4 * 3 * 6144 * 2 * 2 * 2688 * 1856)  # TWO products an assignment
    assert expert_bytes == pytest.approx(4 * (2 * 8 * 2688 * 1856 * 8 + 3 * 6144 * 2688 * 6))
    dense = rooflines_nemotronh.dense_parameters(cfg)
    assert dense == (4 * (2688 * 10_304 + 4096 * 2688) + 2 * 2688 * 4096 + 2 * 2688 * 256
                     + 4 * (2688 * 128 + 2 * 2688 * 3712) + 16384 * 2688)
    total, hbm = rooflines_nemotronh.step(cfg, shape, 666_963_456)
    assert hbm == pytest.approx(666_963_456 * 28)
    assert total == pytest.approx(6 * tokens * dense + core + scan + experts)
    # the issue's arithmetic: 303 M dense parameters a token, about 32 TFLOP a step
    assert 302e6 < dense < 304e6 and 31e12 < total < 34e12


def test_the_readers_find_nothing_without_the_mechanism():
    other = manifest.config("seqrec-granite4h-micro-vp8")
    obs = {"pio_trace": {"devices": {}}, "peaks": PEAKS, "seq_shape": {"config": other}}
    for params in SHARES:
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
        assert seq_model_roofline.read({"pio_trace": None}, {**NEEDS, **params}) is None
    assert seq_products_roofline.read(obs, PRODUCTS) is None
    assert seq_products_roofline.read({"pio_trace": None}, PRODUCTS) is None
    # and on a program without the scopes (the parent has no seq.moe.shared, and this
    # configuration's key is in no other file)
    mine = manifest.config(CONFIG)
    bare = {"devices": {"/device:TPU:0": {"XLA Ops": [("a", 0.0, 1.0)], "XLA Modules": []}},
            "stacks": {"/device:TPU:0": ["jit(step)/seq.ffn/dot"]}, "host": [("bench.window", 0.0, 10.0)]}
    obs = {"pio_trace": bare, "peaks": PEAKS,
           "seq_shape": {"config": mine, "held_by_step": [[1.0] * 4], "tokens": 1, "pair_sum": 1.0,
                         "steps": 1, "n_params": 1.0}}
    for params in SHARES:
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
    assert seq_products_roofline.read(obs, PRODUCTS) is None
    assert not seq_scope_time.read(obs, {"scope": "seq.moe.shared"})
    for name in manifest.benchmark()["configs"]:
        if name["name"] != CONFIG:
            assert "hybrid_override_pattern" not in manifest.config(name["name"])


def test_the_readers_read_a_recorded_trace():
    """The products' reader adds the ``ragged-dot`` custom calls that lie under
    ``seq.moe`` (their own stack names no inner scope) to what lies under
    ``seq.moe.experts``, and no ``ragged-dot`` of another scope."""
    cfg = manifest.config(CONFIG)
    trace = {
        "devices": {"/device:TPU:0": {
            # (an event is named by its instruction's text, as the chip's traces name them)
            "XLA Ops": [("%fusion.1 = f32[8]{0} fusion()", 0.0, 1.0),
                        ("%ragged-dot-none.2 = f32[12288,1856]{1,0} custom-call()", 1.0, 0.5),
                        ("%convert.3 = bf16[8]{0} convert()", 1.5, 0.25),
                        ("ragged-dot-none.4", 2.0, 0.25), ("%fusion.5 = f32[8]{0} fusion()", 2.25, 0.25),
                        ("%ragged-dot-none.6 = f32[8,8]{1,0} custom-call()", 2.5, 0.5),
                        ("%fusion.7 = s32[8]{0} fusion()", 3.0, 0.125)],
            "XLA Modules": [("jit_step(1)", 0.0, 4.0)]}},
        "stacks": {"/device:TPU:0": [
            "jit(step)/transpose(jvp(seq.ssm))/checkpoint/seq.ssm.scan/pallas_call",
            "jit(step)/seq.moe/_one_pass/ragged-dot-none",
            "jit(step)/seq.moe/_one_pass/seq.moe.experts/convert",
            "jit(step)/transpose(jvp(seq.moe))/_pull_pass/ragged-dot-none",
            "jit(step)/seq.moe/seq.moe.shared/dot",
            "jit(step)/seq.ffn/ragged-dot-none",
            "jit(step)/seq.moe/seq.moe.route/sort"]},
        "host": [("bench.window", 0.0, 10.0)],
    }
    tokens = 16384
    shape = {"config": cfg, "tokens": tokens, "steps": 2, "pair_sum": tokens * 100.0,
             "held_by_step": [[6144.0] * 4, [6000.0] * 4], "n_params": 666_963_456.0}
    obs = {"pio_trace": trace, "peaks": PEAKS, "seq_shape": shape}
    assert seq_products_roofline.seconds_of(
        obs["pio_trace"], "seq.moe.experts", "seq.moe", "ragged-dot") == pytest.approx(1.0)
    share = seq_products_roofline.read(obs, PRODUCTS)
    counts = [rooflines_nemotronh.moe_experts(cfg, {**shape, "held": held})
              for held in shape["held_by_step"]]
    least, bound = rooflines.least_time(sum(c[0] for c in counts), sum(c[1] for c in counts), PEAKS)
    assert share == pytest.approx(100 * least / 1.0) and 0 < share < 100
    assert obs["bounds"]["seq.moe.experts"] == bound
    scan = seq_model_roofline.read(obs, {**NEEDS, "model": "ssd_scan", "scope": "seq.ssm.scan"})
    _, hbm = rooflines_nemotronh.ssd_scan(cfg, shape)
    assert scan == pytest.approx(100 * 2 * hbm / 819e9 / 1.0) and obs["bounds"]["seq.ssm.scan"] == "bytes"
    mfu = seq_model_roofline.read(obs, {**NEEDS, "model": "step", "program": "^jit_step"})
    total, _ = rooflines_nemotronh.step(cfg, {**shape, "held": [6072.0] * 4}, 666_963_456.0)
    assert mfu == pytest.approx(100 * total / 197e12 / 4.0) and 0 < mfu < 100
    assert seq_scope_time.read(obs, {"scope": "seq.moe.shared"}) == pytest.approx(0.25)
    assert seq_scope_time.read(obs, {"scope": "seq.moe"}) == pytest.approx(1.375)


def test_the_catalogue_is_the_deployments_and_the_histories_the_seeds():
    """``lib/synth_seq_catalogue.histories``: the lengths are
    ``lib/synth_seq.histories``'s for the seed, to the slot; which ids are
    popular is the same in every run (the first sequence cell's generator deals
    the ranks anew for every seed), and the draws still differ by seed."""
    import numpy as np

    from benchmark.lib import synth_seq, synth_seq_catalogue

    traffic = manifest.workload(CELL)["traffic_params"]
    n_items, tokens = 16384, 8 * 8193

    def top(pieces, k=5):
        return set(np.argsort(-np.bincount(np.concatenate(pieces), minlength=n_items))[:k])

    ours = {seed: synth_seq_catalogue.histories(traffic, n_items, tokens, seed) for seed in (11, 12)}
    theirs = {seed: synth_seq.histories(traffic, n_items, tokens, seed) for seed in (11, 12)}
    for seed in (11, 12):
        assert [len(p) for p in ours[seed]] == [len(p) for p in theirs[seed]]
        assert max(int(p.max()) for p in ours[seed]) < n_items
    assert top(ours[11], 1) == top(ours[12], 1) and len(top(ours[11], 10) & top(ours[12], 10)) >= 9
    assert len(top(theirs[11], 10) & top(theirs[12], 10)) <= 1
    assert not np.array_equal(np.concatenate(ours[11])[:4096], np.concatenate(ours[12])[:4096])
