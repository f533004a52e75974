"""The learned-sparse-attention sequence-training kind off the chip, through
``benchmark/run.py``'s own ``main`` with the rehearsal workload: sound, it says
``correct``; with the index scores' head-weighted sum in bfloat16, with a
choice one key short, one key over or one that reaches across a history, with a
core that ignores the choice, with an indexer that is given the next-item
loss's gradient, with a history allowed to see its neighbour, or with a step
that does not learn, it says not. And the stratified draw of a job's lengths,
and the counts behind its roofline metrics."""

import dataclasses
import json

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import manifest, rooflines, rooflines_keye, synth_seq, synth_seq_strata
from benchmark.readers import seq_model_roofline, seq_scope_time

ARGS = ("--workload", "rehearse-train-seqrec-dsa", "--seconds", "1")
GROUPS = ("attn", "indexer", "router", "experts", "norms", "embed", "head")
READINGS = {
    "loss_err", "index_loss_err", "logit_err", *(f"grad_err.{g}" for g in GROUPS), "index_err",
    "select_err", "dsa_core_err", "update_err", "loss_last_over_first", "window_compiles",
    "dropped", "finite", "router_moved"}
NEEDS = {"lib": "rooflines_keye", "needs": "sa_config"}
CELL = "train-keye-long16k"
SHARES = ({"model": "step", "program": "^jit_step"},
          {"model": "index", "scope": "seq.attn.index"},
          {"model": "core", "scope": "seq.attn.core"})
NEW = {"dsa_index_device_s", "dsa_select_device_s", "dsa_index_loss_device_s",
       "dsa_index_roofline_pct", "dsa_core_roofline_pct", "dsa_kept_pairs_pct", "step_mfu_pct.keye"}
TRAFFIC = dict(length_min=2048, length_exponent=1.1, length_cap=16384, item_exponent=1.0,
               follow_probability=0.5)


def result_of(capsys, *argv):
    assert bench_run.main(list(ARGS + argv)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


def not_ok(lines):
    return {l.split()[2].rstrip(":") for l in lines if "NOT OK" in l}


def broken(capsys, monkeypatch, module, name, replacement, seed="11"):
    """One whole run with ``module.name`` replaced in the program."""
    from predictionio_tpu.models import sequencerec
    from predictionio_tpu.ops import dsa

    monkeypatch.setattr(module, name, replacement)
    sequencerec._programs.cache_clear()  # programs traced before the break
    dsa.select.clear_cache()
    try:
        return result_of(capsys, "--seed", seed, "--trace", "0")
    finally:
        sequencerec._programs.cache_clear()
        dsa.select.clear_cache()


def test_rehearsal_is_correct_and_prints_every_number(capsys):
    result, lines = result_of(capsys, "--seed", "3000000019", "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and result["metrics"] == {}
    assert set(result["rehearsed"]) == {"train_s", "setup_s"}
    compared = {l.split()[2].rstrip(":") for l in lines if l.startswith("[bench] compared ")}
    assert compared == READINGS
    assert any(l.startswith('[bench] mixers: {"dsa": 2}') for l in lines)
    counters = json.loads(next(l for l in lines if l.startswith("[bench] counters: "))[18:])
    assert 0 < counters["pack_fill_pct"] <= 100
    # histories of 28-64 ids under a topk of 24: the choice binds in every one
    assert 50 < counters["dsa_kept_pairs_pct"] < 100 and counters["index_loss"] > 0


def test_traced_rehearsal_reads_the_spans_and_counters(capsys):
    result, lines = result_of(capsys, "--seed", "7", "--trace", "1")
    assert result["correct"] is True
    assert any("4 steps each" in l for l in lines)
    # (the three ``setup_*`` metrics read a process's FIRST job alone: not here)
    assert set(result["rehearsed"]) >= {
        "step_ms", "pack_s", "input_wait_s", "fetch_s", "pack_fill_pct", "dsa_kept_pairs_pct",
        "expert_load_max_over_mean"}
    # nothing of another model's mixer is asked of this cell
    assert not [name for name in manifest.metrics_of(CELL, True)
                if any(word in name for word in ("ssm", "deltanet", "shortconv", "mla", "mamba", "gmu"))]


def test_control_is_not_correct_by_the_index_scores(capsys):
    result, lines = result_of(capsys, "--seed", "5", "--trace", "0", "--control", "bf16_index_scores")
    assert result["correct"] is False
    # index_err: the reading taken from what the timed function's own indexer was handed and gave
    assert "index_err" in not_ok(lines) and "dsa_core_err" not in not_ok(lines)


@pytest.mark.parametrize("wrong", ["one_short", "one_more", "across"])
def test_a_choice_that_is_not_the_sort_is_not_correct(capsys, monkeypatch, wrong):
    """The choice one key short of topk, one key over, and one that lets a
    query choose among the keys of the whole row: ``select_err`` reads it from
    the program's own scores; what follows the choice is compared on the
    program's own sets and stays sound, but for a set that reaches across a
    history. (The order among equal scores is held off the chip, where scores
    can be made to tie: ``tests/test_seq_backbone_keye.py``.)"""
    import jax.numpy as jnp

    from predictionio_tpu.ops import dsa

    real = dsa._strip_choice

    def other(scores, valid, topk):
        if wrong == "across":
            return real(scores, jnp.ones_like(valid), topk)
        return real(scores, valid, topk + (1 if wrong == "one_more" else -1))

    result, lines = broken(capsys, monkeypatch, dsa, "_strip_choice", other)
    assert result["correct"] is False and "select_err" in not_ok(lines)
    assert "index_err" not in not_ok(lines)
    if wrong != "across":  # the core and the network ran soundly on the sets they were given
        assert not {"dsa_core_err", "logit_err", "grad_err.attn"} & not_ok(lines)


def test_a_core_that_ignores_the_choice_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.models import seq_backbone as bb

    real = bb.chosen_attention
    result, lines = broken(
        capsys, monkeypatch, bb, "chosen_attention",
        lambda q, k, v, chosen, seg, **kw: real(q, k, v, chosen | True, seg, **kw))
    assert result["correct"] is False and "dsa_core_err" in not_ok(lines)
    assert not {"index_err", "select_err"} & not_ok(lines)


def test_an_indexer_that_takes_the_next_item_gradient_is_not_correct(capsys, monkeypatch):
    """The ``stop_gradient`` between the layer's normed input and the indexer
    taken away: the indexers' loss now reaches the residual stream."""
    import jax

    result, lines = broken(capsys, monkeypatch, jax.lax, "stop_gradient", lambda x: x)
    assert result["correct"] is False
    assert {"grad_err.embed", "grad_err.norms"} & not_ok(lines)
    assert not {"index_err", "select_err", "dsa_core_err"} & not_ok(lines)


def test_a_history_that_sees_its_neighbour_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.models import seq_backbone as bb

    real = bb.hidden_states

    def merged(cfg, params, tokens, seg, *args, **kwargs):
        return real(cfg, params, tokens, seg * 0 + 1, *args, **kwargs)

    result, lines = broken(capsys, monkeypatch, bb, "hidden_states", merged)
    assert result["correct"] is False and result["failed"] == result["attempted"]
    # (the checked row holds one history and padding: the padding is let in)
    assert {"select_err", "dsa_core_err"} <= not_ok(lines)


def test_a_step_that_does_not_learn_is_not_correct(capsys, monkeypatch):
    import optax

    real = optax.adamw
    result, lines = broken(capsys, monkeypatch, optax, "adamw", lambda rate: real(0.0))
    assert result["correct"] is False
    assert {"loss_last_over_first", "update_err"} <= not_ok(lines)


def test_the_parent_program_is_refused_at_once(capsys, monkeypatch):
    """On a program whose backbone knows no indexer the kind ends the run
    before any job, with a message."""
    from predictionio_tpu.models import seq_backbone as bb

    old = dataclasses.make_dataclass("BackboneConfig", [("hidden_size", int, 64)])
    monkeypatch.setattr(bb, "BackboneConfig", old)
    with pytest.raises(SystemExit) as stopped:
        bench_run.main(list(ARGS + ("--seed", "1", "--trace", "0")))
    assert "no lightning indexer" in str(stopped.value.code)


def test_the_cell_is_the_issues_letter_for_letter():
    cell, cfg = manifest.workload(CELL), manifest.config("seqrec-keye-vl2-30b-a3b-ep8")
    assert (cell["config"], cell["kind"], cell["traffic"], cell["chips"]) == (
        "seqrec-keye-vl2-30b-a3b-ep8", "train_seqrec_dsa", "long-histories-16k-1row", 1)
    joyai = manifest.workload("train-joyai-long8k")["traffic_params"]
    assert cell["traffic_params"] == {
        **TRAFFIC, "length_draw": "stratified", "rows_per_step": 1, "warm_steps": 2,
        "trace_steps": 4, "check": joyai["check"]}
    assert (cfg["algorithm"]["batch_size"], cfg["algorithm"]["seq_len"], cfg["vocab_size"]) == (1, 16384, 18992)
    assert set(cfg["control"]["train"]) == {"bf16_index_scores"}
    assert set(cfg["limits"]["train"]) == READINGS - {"window_compiles", "dropped", "finite", "router_moved"}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    listed = set(manifest.metrics_of(CELL, True))
    assert listed == NEW | {
        "device_idle_pct.train", "fetch_s", "idle_attributed_pct.train", "step_ms", "pack_s",
        "input_wait_s", "attn_device_s", "moe_device_s",
        "head_device_s", "optimizer_device_s", "pack_fill_pct", "expert_load_max_over_mean",
        "setup_trace_lower_s", "setup_backend_s", "setup_cache_misses"}
    assert manifest.metrics_of(CELL, False) == ["train_s", "setup_s"]
    # the new metrics are this cell's alone
    for other in ("train-lfm2-packed8k", "train-joyai-long8k", "train-amazonbooks"):
        assert not NEW & set(manifest.metrics_of(other, True))


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of Keye-VL-2.0-30B-A3B, key by key, but for the
    three keys ``reduced`` names."""
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    cfg = manifest.config("seqrec-keye-vl2-30b-a3b-ep8")
    differs = sorted(key for key, value in catalog.items() if key not in cfg or cfg[key] != value)
    assert differs == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (6, 16, 151936 // 8)
    assert all(cfg["published"][key] == catalog[key] for key in differs)
    assert cfg["experts_held"] == [0, 16] and cfg["router_width"] == 128


@pytest.mark.parametrize("rows", [manifest.config("seqrec-keye-vl2-30b-a3b-ep8")["algorithm"]["steps"], 32])
def test_over_twenty_seeds_a_jobs_causal_pairs_stay_within_two_per_cent(rows):
    """The stratified draw at the cell's law: the causal pairs of the rows a job
    trains on swing by under 2 % (relative sd over twenty seeds, the driver's
    large ones among them; under 1.2 % in fact) where independent draws swing by
    more than train_s's bound of 5 %; every job packs into exactly its rows, holds
    the law's cap and its floor, and the share the choice keeps stays at 36 %
    within a point."""
    seeds = list(range(1, 11)) + [2147483600 + 7 * i for i in range(10)]
    slots, pairs, free, kept = 16385, [], [], []
    for seed in seeds:
        lengths = synth_seq_strata.job_lengths(TRAFFIC, rows, slots, seed).astype(np.float64)
        assert synth_seq_strata.rows_first_fit(lengths, slots) == rows
        assert lengths.max() == 16384 and 2048 <= lengths.min() < 2300
        pairs.append(float((lengths * (lengths + 1) / 2).sum()))
        cut = np.minimum(lengths, 2048)
        kept.append(float((cut * (cut + 1) / 2 + (lengths - cut) * 2048).sum()) / pairs[-1])
        free.append(1.0 - lengths.sum() / (rows * slots))
        rng = np.random.default_rng(seed)
        iid = synth_seq.history_lengths(TRAFFIC, 200, rng).astype(np.float64)
        iid = iid[: int(np.searchsorted(np.cumsum(iid), rows * slots)) + 1]
        free.append(float((iid * (iid + 1) / 2).sum()))
    pairs, iid = np.asarray(pairs), np.asarray(free[1::2])
    assert pairs.std() / pairs.mean() < 0.012 < 0.02 < 0.05 < iid.std() / iid.mean()
    assert max(free[0::2]) < 0.12
    assert 0.35 < min(kept) and max(kept) < 0.37


def test_the_stratified_histories_are_seeded_and_pack_into_the_jobs_rows():
    from predictionio_tpu.models.sequencerec import pack_first_fit

    traffic = {**TRAFFIC, "length_min": 28, "length_cap": 64}
    a = synth_seq_strata.histories(traffic, 50, 12, 65, 2147483999)
    b = synth_seq_strata.histories(traffic, 50, 12, 65, 2147483999)
    c = synth_seq_strata.histories(traffic, 50, 12, 65, 2147484000)
    assert all((x == y).all() for x, y in zip(a, b)) and len(a) == len(b)
    assert [len(x) for x in a] != [len(x) for x in c]
    assert pack_first_fit(a, 65)[0].shape[0] == 12
    assert min(len(x) for x in a) >= 28 and max(len(x) for x in a) == 64
    assert all(0 <= x.min() and x.max() < 50 for x in a)
    # one catalogue for every seed: the same ids are the popular ones, and the
    # same id follows a given id more often than any other does
    ids_a, ids_c = np.concatenate(a), np.concatenate(c)
    assert (np.argsort(-np.bincount(ids_a, minlength=50))[:3]
            == np.argsort(-np.bincount(ids_c, minlength=50))[:3]).all()
    top = int(np.bincount(ids_a).argmax())

    def follower(ids):
        return int(np.bincount(ids[1:][ids[:-1] == top], minlength=50).argmax())

    assert follower(ids_a) == follower(ids_c)
    # one length from each stratum: the sorted lengths are the law's quantiles
    n = len(a)
    edges = synth_seq_strata.quantile_lengths(traffic, np.arange(n + 1) / n - 1e-12 * (np.arange(n + 1) == n))
    assert all(lo <= length <= hi for length, lo, hi in zip(sorted(len(x) for x in a), edges, edges[1:]))


def test_the_step_count_is_the_sum_of_its_parts():
    cfg = manifest.config("seqrec-keye-vl2-30b-a3b-ep8")
    tokens = 16384
    shape = {"tokens": tokens, "pair_sum": tokens * 4500.0, "kept_pair_sum": tokens * 1600.0,
             "held": [16384.0] * 6}
    index, index_bytes = rooflines_keye.index(cfg, shape)
    wide = 16 * 64 + 64 + 16
    assert index == pytest.approx(6 * (6 * tokens * 2048 * wide + tokens * 4500.0 * 16 * (128 + 3)))
    assert index_bytes == pytest.approx(6 * (tokens * 2 * (2048 + wide) * 2 + tokens * 4500.0 / 8))
    core, core_bytes = rooflines_keye.core(cfg, shape)
    assert core == pytest.approx(3 * 6 * 2 * tokens * 1600.0 * 32 * 256)
    assert core_bytes == pytest.approx(6 * tokens * 2 * (2 * 32 * 128 + 2 * 4 * 128) * 2)
    total, hbm = rooflines_keye.step(cfg, shape, 659_190_016)
    assert hbm == pytest.approx(659_190_016 * 28)
    dense = 6 * (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128) + 18992 * 2048
    experts = 6 * 3 * 16384.0 * 3 * 2 * 2048 * 768
    assert total == pytest.approx(6 * tokens * dense + index + core + experts
                                  + 6 * 2 * tokens * 1600.0 * 16 * 131)
    # the cores over the kept pairs are a third of what every causal pair would cost
    assert core / (3 * 6 * 2 * tokens * 4500.0 * 32 * 256) == pytest.approx(1600 / 4500)


def test_the_reader_finds_nothing_without_the_mechanism():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    other = manifest.config("seqrec-lfm2-24b-a2b-ep8")
    obs = {"pio_trace": {"devices": {}}, "peaks": peaks, "seq_shape": {"config": other}}
    for params in SHARES:
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
        assert seq_model_roofline.read({"pio_trace": None}, {**NEEDS, **params}) is None
    # and on a program without the scopes (the parent: no seq.attn.index anywhere)
    mine = manifest.config("seqrec-keye-vl2-30b-a3b-ep8")
    bare = {"devices": {"/device:TPU:0": {"XLA Ops": [("a", 0.0, 1.0)], "XLA Modules": []}},
            "stacks": {"/device:TPU:0": ["jit(step)/seq.moe/seq.moe.experts/dot"]},
            "host": [("bench.window", 0.0, 10.0)]}
    obs = {"pio_trace": bare, "peaks": peaks,
           "seq_shape": {"config": mine, "held_by_step": [[1.0] * 6], "tokens": 1, "pair_sum": 1.0,
                         "kept_pair_sum": 1.0, "steps": 1, "n_params": 1.0}}
    for params in SHARES:
        assert seq_model_roofline.read(obs, {**NEEDS, **params}) is None
    for scope in ("seq.attn.index", "seq.attn.select", "seq.attn.index_loss"):
        assert not seq_scope_time.read(obs, {"scope": scope})


def test_the_readers_read_a_recorded_trace_and_tell_the_four_scopes_apart():
    cfg = manifest.config("seqrec-keye-vl2-30b-a3b-ep8")
    trace = {
        "devices": {"/device:TPU:0": {
            "XLA Ops": [("a", 0.0, 1.0), ("b", 1.0, 1.0), ("c", 2.0, 0.5), ("d", 2.5, 0.25),
                        ("e", 2.75, 0.125), ("f", 2.875, 0.125)],
            "XLA Modules": [("jit_step(1)", 0.0, 3.0)]}},
        "stacks": {"/device:TPU:0": [
            "jit(step)/seq.attn/seq.attn.core/jit(chosen_attention)/while/dot",
            "jit(step)/transpose(jvp(seq.attn))/seq.attn.index_loss/jit(index_loss)/while/dot",
            "jit(step)/seq.attn/jit(select)/while/body/seq.attn.index/dot",
            "jit(step)/seq.attn/jit(select)/while/body/seq.attn.select/reduce",
            "jit(step)/seq.attn/seq.attn.index/dot",
            "jit(step)/seq.moe/seq.moe.experts/ragged-dot"]},
        "host": [("bench.window", 0.0, 10.0)],
    }
    tokens = 16384
    shape = {"config": cfg, "tokens": tokens, "steps": 2, "pair_sum": tokens * 4500.0,
             "kept_pair_sum": tokens * 1600.0, "held_by_step": [[16384.0] * 6] * 2,
             "n_params": 659_190_016.0}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    obs = {"pio_trace": trace, "peaks": peaks, "seq_shape": shape}
    held = {**shape, "held": [16384.0] * 6}
    core = seq_model_roofline.read(obs, {**NEEDS, "model": "core", "scope": "seq.attn.core"})
    least, _ = rooflines.least_time(*(2 * n for n in rooflines_keye.core(cfg, held)), peaks)
    assert core == pytest.approx(100 * least / 1.0) and 0 < core < 100
    index = seq_model_roofline.read(obs, {**NEEDS, "model": "index", "scope": "seq.attn.index"})
    least, _ = rooflines.least_time(*(2 * n for n in rooflines_keye.index(cfg, held)), peaks)
    # the projections and the choice's scores: 0.625 s, not the loss's pass beside them
    assert index == pytest.approx(100 * least / 0.625) and 0 < index < 100
    mfu = seq_model_roofline.read(obs, {**NEEDS, "model": "step", "program": "^jit_step"})
    total, _ = rooflines_keye.step(cfg, held, 659_190_016.0)
    assert mfu == pytest.approx(100 * total / 197e12 / 3.0) and 0 < mfu < 100
    assert seq_scope_time.read(obs, {"scope": "seq.attn.index"}) == pytest.approx(0.625)
    assert seq_scope_time.read(obs, {"scope": "seq.attn.select"}) == pytest.approx(0.25)
    assert seq_scope_time.read(obs, {"scope": "seq.attn.index_loss"}) == pytest.approx(1.0)
    assert seq_scope_time.read(obs, {"scope": "seq.attn.core"}) == pytest.approx(1.0)
    assert seq_scope_time.read(obs, {"scope": "seq.attn"}) == pytest.approx(2.875)
