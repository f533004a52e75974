"""``pio lint`` — the TPU-hygiene static analyzer (predictionio_tpu/lint).

Three layers:

1. **Round-5 fixtures** (``tests/fixtures/lint/``): each of the three
   Mosaic bug classes the round-5 deviceless AOT sweep found (commit
   093d7d2) is reproduced as a bad fixture that must be flagged by
   exactly the intended rule at the marked line — and a clean twin that
   must produce no finding at all (false-positive guard).
2. **Rule semantics**: inline-source tests for the jit-boundary family
   and the suppression machinery.
3. **The self-lint gate**: linting ``predictionio_tpu/`` must yield zero
   unsuppressed findings, and every suppression must carry a reason —
   this is the tier-1 gate that keeps future Pallas PRs from
   reintroducing the round-5 bug classes.

The linter is stdlib-only by design (it must run where jax cannot
import), so these tests never need a device.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from predictionio_tpu.lint import (
    all_rules,
    lint_file,
    lint_paths,
    render_json,
    render_text,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "predictionio_tpu")
FIXTURES = os.path.join(REPO, "tests", "fixtures", "lint")


def _unsuppressed(path: str):
    return [f for f in lint_file(path) if not f.suppressed]


def _marker_line(path: str, marker: str) -> int:
    with open(path) as fh:
        for lineno, line in enumerate(fh, start=1):
            if marker in line:
                return lineno
    raise AssertionError(f"marker {marker!r} not in {path}")


def _package_findings(result, path_suffix: str, rule_prefix: str):
    """Unsuppressed findings for one in-tree file, filtered out of the
    shared module-scoped package sweep — the exemplar pins read the one
    LintResult instead of each re-running the engine."""
    suffix = path_suffix.replace("/", os.sep)
    return [
        f for f in result.findings
        if f.path.endswith(suffix) and f.rule_id.startswith(rule_prefix)
    ]


# ---------------------------------------------------------------------------
# 1. Round-5 Mosaic bug-class fixtures
# ---------------------------------------------------------------------------


class TestRound5Fixtures:
    """Each bad fixture fires exactly its intended rule, at the marked
    line; each clean twin is silent."""

    @pytest.mark.parametrize(
        "fixture,rule_id",
        [
            ("unaligned_lane_slice_bad.py", "mosaic-unaligned-lane-slice"),
            ("rank3_compare_bad.py", "mosaic-rank3-compare"),
            ("per_row_dma_bad.py", "mosaic-per-row-dma"),
        ],
    )
    def test_bad_fixture_fires_exactly_intended_rule(self, fixture, rule_id):
        path = os.path.join(FIXTURES, fixture)
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == [rule_id], (
            f"{fixture}: expected exactly one {rule_id} finding, got "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )
        assert findings[0].line == _marker_line(path, "BAD")

    @pytest.mark.parametrize(
        "fixture",
        [
            "unaligned_lane_slice_clean.py",
            "rank3_compare_clean.py",
            "per_row_dma_clean.py",
        ],
    )
    def test_clean_twin_has_no_findings(self, fixture):
        path = os.path.join(FIXTURES, fixture)
        findings = lint_file(path)
        assert findings == [], (
            f"false positive(s) on clean twin {fixture}: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )


class TestBf16AccumFixtures:
    """``mosaic-bf16-accum`` (the round-12 bf16-gather default's safety
    rule): every contraction shape in the bad twin fires — direct cast,
    the conditional-dtype ``gdt`` idiom, and one-hop taint through a pad
    — the clean twin (kwarg pinned / explicit upcast / no bf16) is
    silent, and the REAL gather-build site in ops/als.py is the clean
    exemplar the rule's message cites."""

    def test_bad_fixture_fires_on_every_contraction(self):
        path = os.path.join(FIXTURES, "bf16_accum_bad.py")
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == ["mosaic-bf16-accum"] * 5, (
            f"expected five mosaic-bf16-accum findings (einsum, "
            f"dot_general, matmul, the @ operator form, and the "
            f"tuple-unpacked operands), got "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_clean_twin_has_no_findings(self):
        path = os.path.join(FIXTURES, "bf16_accum_clean.py")
        findings = lint_file(path)
        assert findings == [], (
            f"false positive(s) on clean twin: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_als_gather_site_is_clean_exemplar(self, package_result):
        """ops/als.py mentions bfloat16 (the rule engages — the
        source-text bail does NOT skip it) yet carries zero findings:
        every normal-equation contraction pins f32 accumulation.
        Judged from the shared package sweep: one engine run serves
        every in-tree exemplar pin."""
        als_path = os.path.join(
            REPO, "predictionio_tpu", "ops", "als.py"
        )
        with open(als_path, encoding="utf-8") as fh:
            assert "bfloat16" in fh.read()
        findings = _package_findings(
            package_result, "ops/als.py", "mosaic-bf16-accum"
        )
        assert findings == [], (
            f"als.py gather build regressed the bf16 accumulation "
            f"contract: {[(f.rule_id, f.line) for f in findings]}"
        )


class TestRobustFixtures:
    """Family C (robustness) bad/clean twins, same contract as the
    round-5 fixtures: the bad file fires exactly its intended rule at
    the marked line, the clean twin is silent."""

    @pytest.mark.parametrize(
        "fixture,rule_id",
        [
            ("no_timeout_bad.py", "robust-no-timeout"),
            ("bare_sleep_retry_bad.py", "robust-bare-sleep-retry"),
            ("rename_no_fsync_bad.py", "robust-rename-no-fsync"),
        ],
    )
    def test_bad_fixture_fires_exactly_intended_rule(self, fixture, rule_id):
        path = os.path.join(FIXTURES, fixture)
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == [rule_id], (
            f"{fixture}: expected exactly one {rule_id} finding, got "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )
        assert findings[0].line == _marker_line(path, "BAD")

    @pytest.mark.parametrize(
        "fixture",
        ["no_timeout_clean.py", "bare_sleep_retry_clean.py",
         "rename_no_fsync_clean.py", "unbounded_retry_clean.py",
         "unbounded_cache_clean.py", "cutover_no_watermark_clean.py",
         "fallback_swallows_clean.py", "nonatomic_checkpoint_clean.py"],
    )
    def test_clean_twin_has_no_findings(self, fixture):
        path = os.path.join(FIXTURES, fixture)
        findings = lint_file(path)
        assert findings == [], (
            f"false positive(s) on clean twin {fixture}: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_unbounded_retry_bad_fires_on_both_loops(self):
        """The bad twin carries TWO unbounded retry shapes (swallow-and-
        continue, swallow-and-log); each fires exactly the intended
        rule at its while line."""
        path = os.path.join(FIXTURES, "unbounded_retry_bad.py")
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == [
            "robust-unbounded-retry", "robust-unbounded-retry"
        ], [(f.rule_id, f.line) for f in findings]
        with open(path) as fh:
            while_lines = [
                lineno for lineno, line in enumerate(fh, start=1)
                if line.strip().startswith("while True")
            ]
        assert [f.line for f in findings] == while_lines

    def test_unbounded_cache_bad_fires_on_both_containers(self):
        """The bad twin carries TWO unbounded cache shapes (locked
        module-global dict, OrderedDict attribute over a class); each
        fires exactly robust-unbounded-cache at its marked store line."""
        path = os.path.join(FIXTURES, "unbounded_cache_bad.py")
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == [
            "robust-unbounded-cache", "robust-unbounded-cache"
        ], [(f.rule_id, f.line) for f in findings]
        with open(path) as fh:
            marked = [
                lineno for lineno, line in enumerate(fh, start=1)
                if "# BAD:" in line
            ]
        assert sorted(f.line for f in findings) == marked

    def test_cutover_no_watermark_bad_fires_on_both_shapes(self):
        """The bad twin carries TWO flip shapes (if/else branch pair,
        bare conditional expression) inside cutover-named functions;
        each fires exactly robust-cutover-no-watermark at its marked
        flip line."""
        path = os.path.join(FIXTURES, "cutover_no_watermark_bad.py")
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == [
            "robust-cutover-no-watermark", "robust-cutover-no-watermark"
        ], [(f.rule_id, f.line) for f in findings]
        with open(path) as fh:
            marked = [
                lineno for lineno, line in enumerate(fh, start=1)
                if "# BAD:" in line
            ]
        assert sorted(f.line for f in findings) == marked

    def test_fallback_swallows_bad_fires_on_both_shapes(self):
        """The bad twin carries TWO swallow shapes (function named for
        the fallback, handler that flips a ``degraded`` flag); each
        fires exactly robust-fallback-swallows at its marked except
        line."""
        path = os.path.join(FIXTURES, "fallback_swallows_bad.py")
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == [
            "robust-fallback-swallows", "robust-fallback-swallows"
        ], [(f.rule_id, f.line) for f in findings]
        with open(path) as fh:
            marked = [
                lineno for lineno, line in enumerate(fh, start=1)
                if "# BAD:" in line
            ]
        assert sorted(f.line for f in findings) == marked

    def test_sharedcache_degrade_is_the_clean_exemplar(self, package_result):
        """fleet/sharedcache.py's client IS wall-to-wall degrade paths
        (every handler calls _record_degrade, so the name gate engages
        on each one) yet carries zero findings: the outcome counter,
        the lastError capture and the debug log are exactly the
        recording evidence the rule demands."""
        findings = _package_findings(
            package_result, "fleet/sharedcache.py",
            "robust-fallback-swallows",
        )
        assert findings == [], (
            f"fleet/sharedcache.py regressed its exemplar status: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_sharedcache_mutated_swallow_is_caught(self):
        """Strip ONE degrade site of its recording (swap the
        _record_degrade call for a bare advisory-named helper, drop the
        bound exception) and the rule bites — proof the exemplar above
        is load-bearing, not accidentally exempt."""
        path = os.path.join(
            PACKAGE, "fleet", "sharedcache.py"
        )
        with open(path) as fh:
            source = fh.read()
        anchor = (
            "except CircuitOpen as exc:\n"
            '            return self._record_degrade("open", exc)'
        )
        mutated = source.replace(
            anchor,
            "except CircuitOpen:\n"
            "            return self._advisory_miss()",
            1,
        )
        assert mutated != source, "mutation anchor drifted out of source"
        findings = [
            f for f in lint_file(path, source=mutated)
            if f.rule_id == "robust-fallback-swallows" and not f.suppressed
        ]
        assert len(findings) == 1, [(f.rule_id, f.line) for f in findings]

    def test_nonatomic_checkpoint_bad_fires_on_all_marked_writes(self):
        """The bad twin carries FOUR raw-write shapes across two
        checkpoint-marked scopes (np.save to the final path, open-w +
        json.dump, open-wb in a persist method); each fires exactly
        robust-nonatomic-checkpoint at its marked line."""
        path = os.path.join(FIXTURES, "nonatomic_checkpoint_bad.py")
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == [
            "robust-nonatomic-checkpoint"
        ] * 4, [(f.rule_id, f.line) for f in findings]
        with open(path) as fh:
            marked = [
                lineno for lineno, line in enumerate(fh, start=1)
                if "# BAD:" in line
            ]
        assert sorted(f.line for f in findings) == marked

    def test_ckpt_store_is_the_clean_exemplar(self, package_result):
        """ckpt/store.py's save path IS the rule's target shape (the
        name gate engages on save/_save_files, both write checkpoint
        files) yet carries zero findings: every byte goes through
        atomic_write_bytes, which is exactly the commit evidence the
        rule demands."""
        findings = _package_findings(
            package_result, "ckpt/store.py",
            "robust-nonatomic-checkpoint",
        )
        assert findings == [], (
            f"ckpt/store.py regressed its exemplar status: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_ckpt_store_mutated_raw_write_is_caught(self):
        """Swap the store's one atomic per-file write for a raw
        open().write() and the rule bites — proof the exemplar above is
        load-bearing, not accidentally exempt."""
        path = os.path.join(PACKAGE, "ckpt", "store.py")
        with open(path) as fh:
            source = fh.read()
        anchor = "atomic_write_bytes(os.path.join(d, fname), data)"
        mutated = source.replace(
            anchor,
            'open(os.path.join(d, fname), "wb").write(data)',
            1,
        )
        assert mutated != source, "mutation anchor drifted out of source"
        findings = [
            f for f in lint_file(path, source=mutated)
            if f.rule_id == "robust-nonatomic-checkpoint"
            and not f.suppressed
        ]
        assert len(findings) == 1, [(f.rule_id, f.line) for f in findings]

    def test_migration_cutover_is_the_clean_exemplar(self, package_result):
        """storage/migration.py's cutover() IS a layout flip (the name
        gate engages, self._active is assigned one store per branch)
        yet carries zero findings: the freeze, the final drain_queue
        and the per-keyspace watermark loop ahead of the flip are the
        barrier evidence the rule demands."""
        findings = _package_findings(
            package_result, "storage/migration.py",
            "robust-cutover-no-watermark",
        )
        assert findings == [], (
            f"storage/migration.py regressed its exemplar status: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_response_cache_is_the_clean_exemplar(self, package_result):
        """fleet/cache.py IS a cache (the name gate engages, it stores
        under request-derived keys) yet carries zero findings: the LRU
        popitem under the len() bound and the TTL/epoch drops are the
        eviction evidence the rule demands."""
        findings = _package_findings(
            package_result, "fleet/cache.py", "robust-unbounded-cache"
        )
        assert findings == [], (
            f"fleet/cache.py regressed its own bound: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )


#: family E/F fixture slug → the one rule its bad twin must trip
_CONC_FIXTURES = [
    ("unguarded_attr", "conc-unguarded-attr"),
    ("acquire_no_with", "conc-acquire-no-with"),
    ("blocking_under_lock", "conc-blocking-under-lock"),
    ("lock_order", "conc-lock-order"),
    ("module_mutable", "conc-module-mutable"),
    ("contextvar_thread_hop", "conc-contextvar-thread-hop"),
]

_SPMD_FIXTURES = [
    ("collective_host_branch", "spmd-collective-host-branch"),
    ("axis_name_mismatch", "spmd-axis-name-mismatch"),
    ("spec_rank_mismatch", "spmd-spec-rank-mismatch"),
    ("shard_map_arity", "spmd-shard-map-arity"),
    ("unordered_operand", "spmd-unordered-collective-operand"),
    ("host_dependent_rng", "spmd-host-dependent-rng"),
    ("collective_missing_axis", "spmd-collective-missing-axis"),
    # the *args-forwarding direction: judged through the call graph
    # (family G's deep component shares the per-file rule's id)
    ("collective_vararg_axis", "spmd-collective-missing-axis"),
    ("unguarded_downcast", "spmd-unguarded-downcast"),
]

#: family G (cross-file flow) fixture slug → its rule — single-file
#: twins work through lint_file's one-module package context
_FLOW_FIXTURES = [
    ("flow_blocking_under_lock", "flow-blocking-under-lock"),
    ("flow_deadline_dropped", "flow-deadline-dropped"),
    ("flow_thread_leak", "flow-thread-leak"),
]


class TestShardedTrainerExemplar:
    """ops/als_sharded.py is the spmd family's clean exemplar BY TEST:
    its shard_map-mapped body carries a psum + all_gather the rules
    genuinely inspect (proven by mutating the source: stripping the
    psum's axis makes the new rule fire), and the real file is clean."""

    _PSUM_CALL = "jax.lax.psum(local_yty, SHARD_AXIS)"

    def _path(self):
        return os.path.join(
            REPO, "predictionio_tpu", "ops", "als_sharded.py"
        )

    def test_sharded_trainer_is_clean(self, package_result):
        findings = _package_findings(
            package_result, "ops/als_sharded.py", "spmd-"
        )
        assert findings == [], (
            f"als_sharded.py regressed the spmd contract: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_rule_genuinely_engages_on_the_trainer(self):
        """Strip the Gramian psum's axis argument and the new rule must
        fire — the exemplar is inside the rule's scope, not skipped."""
        with open(self._path(), encoding="utf-8") as fh:
            src = fh.read()
        assert self._PSUM_CALL in src  # the collective the pin rides on
        mutated = src.replace(self._PSUM_CALL, "jax.lax.psum(local_yty)")
        findings = [
            f
            for f in lint_file(self._path(), source=mutated)
            if f.rule_id == "spmd-collective-missing-axis"
        ]
        assert len(findings) == 1, (
            f"expected the axis-stripped psum to fire exactly once, got "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )


class TestQuantTableExemplar:
    """quant/table.py is spmd-unguarded-downcast's clean exemplar BY
    TEST: ``quantize_serving_table`` is serve-marked AND narrows to int8
    in-scope, yet carries zero findings because ``topk_match_gate`` sits
    in the same scope — the cut-precision-AND-measure adjacency the rule
    demands. The mutation proves the rule genuinely inspects it."""

    _GATE_CALL = "match_rate = topk_match_gate("

    def _path(self):
        return os.path.join(
            REPO, "predictionio_tpu", "quant", "table.py"
        )

    def test_quant_table_is_clean(self, package_result):
        findings = _package_findings(
            package_result, "quant/table.py", "spmd-"
        )
        assert findings == [], (
            f"quant/table.py regressed its exemplar status: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_rule_genuinely_engages_on_the_table(self):
        """Swap the gate call for a non-gate-shaped name and the rule
        must fire on the inlined int8 encode — the exemplar is inside
        the rule's scope, not skipped."""
        with open(self._path(), encoding="utf-8") as fh:
            src = fh.read()
        assert self._GATE_CALL in src  # the gate the pin rides on
        mutated = src.replace(self._GATE_CALL, "match_rate = probe_overlap(")
        findings = [
            f
            for f in lint_file(self._path(), source=mutated)
            if f.rule_id == "spmd-unguarded-downcast"
        ]
        assert len(findings) == 1, (
            f"expected the ungated int8 encode to fire exactly once, got "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )


class TestConcSpmdFixtures:
    """Family E (concurrency) and family F (SPMD) bad/clean twins, same
    contract as the other families: the bad twin fires exactly its
    intended rule at the marked line, the clean twin is silent under the
    FULL rule set (no cross-family false positives)."""

    @pytest.mark.parametrize(
        "slug,rule_id", _CONC_FIXTURES + _SPMD_FIXTURES + _FLOW_FIXTURES
    )
    def test_bad_fixture_fires_exactly_intended_rule(self, slug, rule_id):
        path = os.path.join(FIXTURES, f"{slug}_bad.py")
        findings = _unsuppressed(path)
        assert [f.rule_id for f in findings] == [rule_id], (
            f"{slug}: expected exactly one {rule_id} finding, got "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )
        assert findings[0].line == _marker_line(path, "BAD")

    @pytest.mark.parametrize(
        "slug",
        [s for s, _ in _CONC_FIXTURES + _SPMD_FIXTURES + _FLOW_FIXTURES],
    )
    def test_clean_twin_has_no_findings(self, slug):
        path = os.path.join(FIXTURES, f"{slug}_clean.py")
        findings = lint_file(path)
        assert findings == [], (
            f"false positive(s) on clean twin {slug}: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    @pytest.mark.parametrize(
        "slug,rule_id",
        [_CONC_FIXTURES[0], _SPMD_FIXTURES[0]],
        ids=["conc", "spmd"],
    )
    def test_suppression_without_reason_is_a_finding(self, slug, rule_id):
        """Per-family: a bare suppression on a family E/F finding is
        itself a finding — the reason stays mandatory for the new
        families."""
        path = os.path.join(FIXTURES, f"{slug}_bad.py")
        with open(path) as fh:
            lines = fh.read().splitlines()
        marker = _marker_line(path, "BAD") - 1
        code = lines[marker].split("#")[0].rstrip()
        lines[marker] = f"{code}  # pio: lint-ok[{rule_id}]"
        findings = lint_file(path, source="\n".join(lines) + "\n")
        unsuppressed = {f.rule_id for f in findings if not f.suppressed}
        assert "lint-suppression-missing-reason" in unsuppressed
        suppressed = [f for f in findings if f.suppressed]
        assert [f.rule_id for f in suppressed] == [rule_id]

    @pytest.mark.parametrize(
        "slug,rule_id",
        [_CONC_FIXTURES[0], _SPMD_FIXTURES[0]],
        ids=["conc", "spmd"],
    )
    def test_suppression_with_reason_suppresses(self, slug, rule_id):
        path = os.path.join(FIXTURES, f"{slug}_bad.py")
        with open(path) as fh:
            lines = fh.read().splitlines()
        marker = _marker_line(path, "BAD") - 1
        code = lines[marker].split("#")[0].rstrip()
        lines[marker] = f"{code}  # pio: lint-ok[{rule_id}] reviewed"
        findings = lint_file(path, source="\n".join(lines) + "\n")
        assert [f.rule_id for f in findings if not f.suppressed] == []
        assert [f.rule_id for f in findings if f.suppressed] == [rule_id]


# ---------------------------------------------------------------------------
# 2. Rule semantics (inline sources)
# ---------------------------------------------------------------------------


def _lint_source(source: str, path: str = "predictionio_tpu/x.py"):
    return lint_file(path, source=source)


class TestJitRules:
    def test_python_branch_on_traced_arg_fires(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x > 0:\n"
            "        return x\n"
            "    return -x\n"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["jit-python-branch"]
        assert findings[0].line == 4

    def test_branch_on_static_arg_is_clean(self):
        src = (
            "import functools, jax\n"
            "@functools.partial(jax.jit, static_argnames=('flag',))\n"
            "def f(x, flag):\n"
            "    if flag:\n"
            "        return x\n"
            "    return -x\n"
        )
        assert _lint_source(src) == []

    def test_branch_on_shape_facet_is_clean(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    if x.shape[0] > 8:\n"
            "        return x[:8]\n"
            "    return x\n"
        )
        assert _lint_source(src) == []

    def test_jit_in_loop_fires(self):
        src = (
            "import jax\n"
            "def warm(fns):\n"
            "    out = []\n"
            "    for fn in fns:\n"
            "        out.append(jax.jit(fn))\n"
            "    return out\n"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["jit-in-loop"]

    def test_host_sync_scoped_to_hot_path_modules(self):
        src = (
            "def respond(result):\n"
            "    return result.block_until_ready()\n"
        )
        hot = _lint_source(src, path="predictionio_tpu/workflow/serving.py")
        assert [f.rule_id for f in hot] == ["jit-host-sync-serving"]
        # same source outside the hot path: no finding
        assert _lint_source(src, path="predictionio_tpu/ops/als.py") == []

    def test_module_level_device_array_fires(self):
        src = (
            "import jax.numpy as jnp\n"
            "SCALE = jnp.ones((8, 128))\n"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["jit-module-device-array"]

    def test_nonhashable_static_default_fires(self):
        src = (
            "import functools, jax\n"
            "@functools.partial(jax.jit, static_argnames=('opts',))\n"
            "def f(x, opts=[]):\n"
            "    return x\n"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["jit-nonhashable-static"]


class TestRobustRules:
    def test_requests_without_timeout_fires(self):
        src = (
            "import requests\n"
            "def post(url, data):\n"
            "    return requests.post(url, json=data)\n"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["robust-no-timeout"]

    def test_requests_with_timeout_is_clean(self):
        src = (
            "import requests\n"
            "def post(url, data):\n"
            "    return requests.post(url, json=data, timeout=10)\n"
        )
        assert _lint_source(src) == []

    def test_kwargs_splat_gets_benefit_of_the_doubt(self):
        src = (
            "import requests\n"
            "def post(url, **kw):\n"
            "    return requests.post(url, **kw)\n"
        )
        assert _lint_source(src) == []

    def test_urlopen_positional_timeout_is_clean(self):
        src = (
            "import urllib.request\n"
            "def get(url):\n"
            "    return urllib.request.urlopen(url, None, 5).read()\n"
        )
        assert _lint_source(src) == []

    def test_urlopen_without_timeout_fires(self):
        src = (
            "import urllib.request\n"
            "def get(url):\n"
            "    return urllib.request.urlopen(url).read()\n"
        )
        assert [f.rule_id for f in _lint_source(src)] == ["robust-no-timeout"]

    def test_http_connection_without_timeout_fires(self):
        src = (
            "import http.client\n"
            "def conn(host):\n"
            "    return http.client.HTTPConnection(host, 80)\n"
        )
        assert [f.rule_id for f in _lint_source(src)] == ["robust-no-timeout"]

    def test_constant_sleep_in_retry_loop_fires(self):
        src = (
            "import time\n"
            "def poll(fn):\n"
            "    while True:\n"
            "        try:\n"
            "            return fn()\n"
            "        except OSError:\n"
            "            time.sleep(5)\n"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["robust-bare-sleep-retry"]
        assert findings[0].line == 7

    def test_variable_delay_sleep_is_clean(self):
        # a computed (e.g. jittered) delay is exactly the fix — no finding
        src = (
            "import random, time\n"
            "def poll(fn, base):\n"
            "    while True:\n"
            "        try:\n"
            "            return fn()\n"
            "        except OSError:\n"
            "            time.sleep(random.uniform(0, base))\n"
        )
        assert _lint_source(src) == []

    def test_pacing_sleep_outside_except_is_clean(self):
        src = (
            "import time\n"
            "def drain(pending):\n"
            "    while pending():\n"
            "        time.sleep(0.005)\n"
        )
        assert _lint_source(src) == []

    def test_sleep_in_except_outside_any_loop_is_clean(self):
        src = (
            "import time\n"
            "def once(fn):\n"
            "    try:\n"
            "        return fn()\n"
            "    except OSError:\n"
            "        time.sleep(1)\n"
        )
        assert _lint_source(src) == []

    def test_one_shot_fallback_defined_inside_a_loop_is_clean(self):
        # a def nested in a loop body is a NEW scope: its one-shot
        # except/sleep is not part of the loop's retry schedule
        src = (
            "import time\n"
            "def wire(fns):\n"
            "    out = []\n"
            "    for fn in fns:\n"
            "        def once(fn=fn):\n"
            "            try:\n"
            "                return fn()\n"
            "            except OSError:\n"
            "                time.sleep(1)\n"
            "        out.append(once)\n"
            "    return out\n"
        )
        assert _lint_source(src) == []


class TestMosaicRuleScoping:
    def test_blockspec_tiling_fires_on_unaligned_literal(self):
        src = (
            "from jax.experimental import pallas as pl\n"
            "def call(x):\n"
            "    return pl.pallas_call(\n"
            "        _k,\n"
            "        in_specs=[pl.BlockSpec((8, 56), lambda i: (i, 0))],\n"
            "    )(x)\n"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["mosaic-blockspec-tiling"]

    def test_smem_blockspec_exempt(self):
        src = (
            "from jax.experimental import pallas as pl\n"
            "from jax.experimental.pallas import tpu as pltpu\n"
            "def call(x):\n"
            "    return pl.pallas_call(\n"
            "        _k,\n"
            "        in_specs=[pl.BlockSpec((4, 60), lambda i: (i, 0),\n"
            "                               memory_space=pltpu.SMEM)],\n"
            "    )(x)\n"
        )
        assert _lint_source(src) == []

    def test_non_kernel_function_not_scanned_for_lane_slices(self):
        # pl.ds-looking code outside any pallas_call kernel: Family A
        # does not apply (host-side helpers may slice freely)
        src = (
            "import jax.numpy as jnp\n"
            "def host_helper(x_ref):\n"
            "    return x_ref[:, 3:19]\n"
        )
        assert _lint_source(src) == []


class TestSuppressions:
    BAD_KERNEL = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "def _k(x_ref, o_ref):\n"
        "    o_ref[:] = x_ref[:, pl.ds(16, 16)]{comment}\n"
        "def call(x, out_shape):\n"
        "    return pl.pallas_call(_k, out_shape=out_shape)(x)\n"
    )

    def test_suppression_with_reason_suppresses(self):
        src = self.BAD_KERNEL.format(
            comment="  # pio: lint-ok[mosaic-unaligned-lane-slice] fixture"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["mosaic-unaligned-lane-slice"]
        assert findings[0].suppressed
        assert findings[0].suppress_reason == "fixture"

    def test_suppression_on_line_above_applies(self):
        src = self.BAD_KERNEL.replace(
            "    o_ref[:] = x_ref[:, pl.ds(16, 16)]{comment}\n",
            "    # pio: lint-ok[mosaic-unaligned-lane-slice] one above\n"
            "    o_ref[:] = x_ref[:, pl.ds(16, 16)]\n",
        )
        findings = _lint_source(src)
        assert [f.suppressed for f in findings] == [True]

    def test_bare_suppression_is_itself_a_finding(self):
        src = self.BAD_KERNEL.format(
            comment="  # pio: lint-ok[mosaic-unaligned-lane-slice]"
        )
        findings = _lint_source(src)
        ids = {f.rule_id for f in findings if not f.suppressed}
        assert "lint-suppression-missing-reason" in ids

    def test_wrong_rule_id_does_not_suppress(self):
        src = self.BAD_KERNEL.format(
            comment="  # pio: lint-ok[mosaic-rank3-compare] wrong id"
        )
        findings = [f for f in _lint_source(src) if not f.suppressed]
        assert "mosaic-unaligned-lane-slice" in [f.rule_id for f in findings]

    def test_unused_suppression_is_reported_stale(self):
        src = (
            "import jax.numpy as jnp\n"
            "# pio: lint-ok[jit-in-loop] exception long since fixed\n"
            "def f(x):\n"
            "    return x\n"
        )
        findings = _lint_source(src)
        assert [f.rule_id for f in findings] == ["lint-unused-suppression"]

    def test_select_cannot_manufacture_staleness(self):
        # the suppression's rule did not run, so its use is unknowable —
        # no stale report
        src = (
            "# pio: lint-ok[jit-in-loop] exception long since fixed\n"
            "def f(x):\n"
            "    return x\n"
        )
        from predictionio_tpu.lint import all_rules as _all

        rules = [r for r in _all() if r.id == "jit-python-branch"]
        findings = lint_file("predictionio_tpu/x.py", rules=rules, source=src)
        assert findings == []

    def test_trailing_suppression_does_not_cover_next_line(self):
        # a suppression trailing code on line N covers line N only; the
        # same-rule violation on line N+1 must still be reported
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "def _k(x_ref, o_ref):\n"
            "    a = x_ref[:, pl.ds(16, 16)]  "
            "# pio: lint-ok[mosaic-unaligned-lane-slice] reviewed\n"
            "    b = x_ref[:, pl.ds(32, 16)]\n"
            "    o_ref[:] = a + b\n"
            "def call(x, out_shape):\n"
            "    return pl.pallas_call(_k, out_shape=out_shape)(x)\n"
        )
        findings = _lint_source(src)
        unsuppressed = [f for f in findings if not f.suppressed]
        assert [(f.rule_id, f.line) for f in unsuppressed] == [
            ("mosaic-unaligned-lane-slice", 6)
        ]

    def test_pattern_in_string_literal_is_not_a_suppression(self):
        # the pattern inside a string on the line directly above the
        # finding — only a real comment may suppress
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "from jax.experimental import pallas as pl\n"
            "def _k(x_ref, o_ref):\n"
            '    doc = "# pio: lint-ok[mosaic-unaligned-lane-slice] ok"\n'
            "    o_ref[:] = x_ref[:, pl.ds(16, 16)]\n"
            "def call(x, out_shape):\n"
            "    return pl.pallas_call(_k, out_shape=out_shape)(x)\n"
        )
        unsuppressed = [f for f in _lint_source(src) if not f.suppressed]
        assert [f.rule_id for f in unsuppressed] == [
            "mosaic-unaligned-lane-slice"
        ]


# ---------------------------------------------------------------------------
# 3. CLI contract + the self-lint gate
# ---------------------------------------------------------------------------


def _run_cli(*argv: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "predictionio_tpu.tools.lint", *argv],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )


class TestCLI:
    def test_exit_nonzero_on_unsuppressed_findings(self):
        proc = _run_cli(os.path.join(FIXTURES, "rank3_compare_bad.py"))
        assert proc.returncode == 1
        assert "mosaic-rank3-compare" in proc.stdout

    def test_exit_zero_on_clean_file(self):
        proc = _run_cli(os.path.join(FIXTURES, "rank3_compare_clean.py"))
        assert proc.returncode == 0

    def test_closed_pipe_dies_quietly(self, tmp_path):
        # `pio lint ... | head` closes stdout early: no traceback may
        # reach stderr (the old behavior raised BrokenPipeError out of
        # print at interpreter exit)
        for i in range(250):
            (tmp_path / f"f{i}.py").write_text(
                open(
                    os.path.join(FIXTURES, "unaligned_lane_slice_bad.py")
                ).read()
            )
        proc = subprocess.run(
            f"{sys.executable} -m predictionio_tpu.tools.lint "
            f"{tmp_path} | head -c 100 > /dev/null",
            shell=True, capture_output=True, text=True, cwd=REPO,
            timeout=120,
        )
        assert "Traceback" not in proc.stderr, proc.stderr[-2000:]

    def test_nonexistent_path_is_an_engine_error(self):
        # a typo'd target must never read as lint-clean — and it is an
        # ENGINE error (exit 2), not a finding (exit 1): the run proved
        # nothing
        proc = _run_cli("no/such/dir_xyz")
        assert proc.returncode == 2
        assert "no such file or directory" in proc.stdout

    def test_json_format_is_machine_readable(self):
        proc = _run_cli(
            os.path.join(FIXTURES, "per_row_dma_bad.py"), "--format", "json"
        )
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        assert doc["ok"] is False
        assert [f["rule"] for f in doc["findings"]] == ["mosaic-per-row-dma"]
        assert doc["findings"][0]["path"].endswith("per_row_dma_bad.py")

    def test_select_restricts_rules(self):
        proc = _run_cli(
            os.path.join(FIXTURES, "per_row_dma_bad.py"),
            "--select", "mosaic-rank3-compare",
        )
        assert proc.returncode == 0  # the only finding is a per-row-dma

    def test_list_rules_covers_all_families(self):
        proc = _run_cli("--list-rules")
        assert proc.returncode == 0
        assert "mosaic-unaligned-lane-slice" in proc.stdout
        assert "jit-python-branch" in proc.stdout
        assert "conc-unguarded-attr" in proc.stdout
        assert "spmd-collective-host-branch" in proc.stdout

    def test_unreadable_file_is_a_parse_error_not_a_crash(self, tmp_path):
        # null bytes raise ValueError from ast.parse; the run must record
        # a parse error and exit 2 (engine error), not hand the watcher
        # a traceback
        bad = tmp_path / "nul.py"
        bad.write_bytes(b"x = 1\x00\n")
        proc = _run_cli(str(tmp_path))
        assert proc.returncode == 2
        assert "parse-error" in proc.stdout
        assert "Traceback" not in proc.stderr

    def test_hidden_and_vendored_dirs_are_pruned(self, tmp_path):
        venv = tmp_path / ".venv"
        venv.mkdir()
        (venv / "vendored.py").write_text(
            "import jax.numpy as jnp\nX = jnp.ones((8, 128))\n"
        )
        (tmp_path / "ok.py").write_text("x = 1\n")
        proc = _run_cli(str(tmp_path))
        assert proc.returncode == 0
        assert "1 files" in proc.stdout

    def test_hot_path_scoping_survives_relative_invocation(
        self, tmp_path, monkeypatch
    ):
        # the `cd workflow && pio lint serving.py` shape: path-scoped
        # rules must see the module identity through a bare filename
        wf = tmp_path / "workflow"
        wf.mkdir()
        (wf / "serving.py").write_text(
            "def respond(r):\n    return r.block_until_ready()\n"
        )
        monkeypatch.chdir(wf)
        findings = lint_file("serving.py")
        assert [f.rule_id for f in findings] == ["jit-host-sync-serving"]

    def test_console_subcommand_dispatches(self):
        # `pio lint` rides bin/pio -> tools.console -> tools.lint; the
        # console path must work without a storage plane or jax import
        proc = subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.tools.console",
             "lint", os.path.join(FIXTURES, "rank3_compare_bad.py")],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert proc.returncode == 1
        assert "mosaic-rank3-compare" in proc.stdout


class TestChangedAndBaseline:
    """``pio lint --changed`` (git-diff-scoped) and ``--baseline``
    (adopt/ratchet), plus the pinned exit-code contract: 0 clean,
    1 findings, 2 engine error.

    These call ``tools.lint.main`` in-process (exit code = return
    value, output via capsys): the subprocess transport is already
    covered by TestCLI, and a fresh interpreter per case would cost
    the tier-1 budget ~20 s for no extra coverage."""

    BAD = os.path.join(FIXTURES, "rank3_compare_bad.py")
    CLEAN = os.path.join(FIXTURES, "rank3_compare_clean.py")

    def _run(self, capsys, *argv):
        from predictionio_tpu.tools import lint as lint_cli

        rc = lint_cli.main(list(argv))
        return rc, capsys.readouterr().out

    def test_exit_codes_pinned(self, tmp_path, capsys):
        assert self._run(capsys, self.CLEAN)[0] == 0
        assert self._run(capsys, self.BAD)[0] == 1
        nul = tmp_path / "nul.py"
        nul.write_bytes(b"x\x00\n")
        assert self._run(capsys, str(nul))[0] == 2

    def _git(self, cwd, *args):
        return subprocess.run(
            ["git", *args], cwd=cwd, capture_output=True, text=True,
            timeout=30,
        )

    def _make_repo(self, tmp_path):
        repo = tmp_path / "repo"
        repo.mkdir()
        assert self._git(repo, "init", "-q").returncode == 0
        self._git(repo, "config", "user.email", "t@example.com")
        self._git(repo, "config", "user.name", "t")
        return repo

    def test_changed_lints_only_git_modified_files(
        self, tmp_path, capsys, monkeypatch
    ):
        repo = self._make_repo(tmp_path)
        monkeypatch.chdir(repo)
        # a committed file WITH a violation: out of scope for --changed
        (repo / "legacy.py").write_text(open(self.BAD).read())
        self._git(repo, "add", "legacy.py")
        assert self._git(repo, "commit", "-qm", "seed").returncode == 0
        rc, out = self._run(capsys, "--changed", str(repo))
        assert rc == 0, out
        assert "no changed files" in out
        # an uncommitted (untracked) violation IS in scope
        (repo / "fresh.py").write_text(open(self.BAD).read())
        rc, out = self._run(capsys, "--changed", str(repo))
        assert rc == 1, out
        assert "fresh.py" in out
        assert "legacy.py" not in out
        assert "1 files" in out
        # a modified tracked file joins the scope too
        (repo / "legacy.py").write_text(
            open(self.BAD).read() + "\nX = 1\n"
        )
        _rc, out = self._run(capsys, "--changed", str(repo))
        assert "2 files" in out

    def test_changed_outside_a_git_repo_is_an_engine_error(
        self, tmp_path, capsys, monkeypatch
    ):
        # a silent empty set would read as "clean" — it must be exit 2
        lone = tmp_path / "lone"
        lone.mkdir()
        monkeypatch.chdir(lone)
        monkeypatch.setenv("GIT_CEILING_DIRECTORIES", str(tmp_path))
        rc, out = self._run(capsys, "--changed", str(lone))
        assert rc == 2, out
        assert "--changed" in out

    def test_baseline_adopts_then_ratchets(self, tmp_path, capsys):
        # adopt: record today's findings; the same run is then clean
        rc, recorded = self._run(capsys, self.BAD, "--format", "json")
        assert rc == 1
        baseline = tmp_path / "baseline.json"
        baseline.write_text(recorded)
        rc, out = self._run(capsys, self.BAD, "--baseline", str(baseline))
        assert rc == 0, out
        assert "1 baselined" in out
        doc = json.loads(recorded)
        assert [f["rule"] for f in doc["findings"]] == [
            "mosaic-rank3-compare"
        ]
        # different path: the baseline keys on (path, rule), so the
        # same content elsewhere is NEW debt, not adopted
        grown = tmp_path / "grown.py"
        grown.write_text(open(self.BAD).read())
        rc, _out = self._run(
            capsys, str(grown), "--baseline", str(baseline)
        )
        assert rc == 1

    def test_baseline_same_path_absorbs_only_the_recorded_count(
        self, tmp_path, capsys
    ):
        bad_src = open(self.BAD).read()
        target = tmp_path / "mod.py"
        target.write_text(bad_src)
        _rc, recorded = self._run(capsys, str(target), "--format", "json")
        baseline = tmp_path / "baseline.json"
        baseline.write_text(recorded)
        # same content: adopted clean
        assert self._run(
            capsys, str(target), "--baseline", str(baseline)
        )[0] == 0
        # duplicate the kernel under new names -> more findings of the
        # same rule in the same file than the baseline recorded: fails
        clone = bad_src.replace("_mask_kernel", "_mask_kernel2").replace(
            "def run(", "def run2("
        )
        target.write_text(bad_src + "\n\n" + clone)
        rc, out = self._run(
            capsys, str(target), "--baseline", str(baseline)
        )
        assert rc == 1, out

    def test_baseline_unreadable_is_an_engine_error(self, tmp_path, capsys):
        missing = tmp_path / "nope.json"
        assert self._run(
            capsys, self.CLEAN, "--baseline", str(missing)
        )[0] == 2
        bad_json = tmp_path / "bad.json"
        bad_json.write_text("{\"not\": \"findings\"}")
        assert self._run(
            capsys, self.CLEAN, "--baseline", str(bad_json)
        )[0] == 2

    def test_baselined_findings_are_reported_in_json(
        self, tmp_path, capsys
    ):
        _rc, recorded = self._run(capsys, self.BAD, "--format", "json")
        baseline = tmp_path / "baseline.json"
        baseline.write_text(recorded)
        rc, out = self._run(
            capsys, self.BAD, "--baseline", str(baseline),
            "--format", "json",
        )
        assert rc == 0
        doc = json.loads(out)
        assert doc["findings"] == []
        assert [f["rule"] for f in doc["baselined"]] == [
            "mosaic-rank3-compare"
        ]


@pytest.fixture(scope="module")
def package_result():
    """ONE package sweep shared by every gate assertion: the sweep is
    the expensive part (~15 s over 100+ files), the assertions are
    free — three tests each doing their own sweep cost the tier-1
    budget ~30 s for identical coverage."""
    return lint_paths([PACKAGE])


class TestSelfLintGate:
    """The tier-1 gate: the package itself must stay lint-clean. A new
    Pallas PR that reintroduces a round-5 bug class fails here before it
    ever reaches a compile."""

    def test_package_has_zero_unsuppressed_findings(self, package_result):
        result = package_result
        assert result.errors == [], result.errors
        assert result.findings == [], (
            "unsuppressed lint findings in the package:\n"
            + render_text(result)
        )

    def test_every_suppression_carries_a_reason(self, package_result):
        result = package_result
        missing = [f for f in result.suppressed if not f.suppress_reason]
        assert missing == [], [f.as_dict() for f in missing]

    def test_families_e_f_and_g_are_in_the_gate(self):
        """The self-lint gate runs ``all_rules()``; every conc-*/spmd-*/
        flow-* rule must be registered there (a family that quietly
        drops out of the default set stops gating anything)."""
        ids = {r.id for r in all_rules()}
        for _slug, rule_id in (
            _CONC_FIXTURES + _SPMD_FIXTURES + _FLOW_FIXTURES
        ):
            assert rule_id in ids, f"{rule_id} missing from all_rules()"
        assert sum(1 for i in ids if i.startswith("conc-")) >= 6
        assert sum(1 for i in ids if i.startswith("spmd-")) >= 7
        assert sum(1 for i in ids if i.startswith("flow-")) >= 3

    def test_rule_catalog_is_documented(self):
        """docs/lint.md is the catalog the suppression workflow points
        people at — every shipped rule id must appear there."""
        with open(os.path.join(REPO, "docs", "lint.md")) as fh:
            doc = fh.read()
        for rule in all_rules():
            assert rule.id in doc, f"rule {rule.id} missing from docs/lint.md"

    def test_json_reporter_roundtrips_package_result(self, package_result):
        result = package_result
        doc = json.loads(render_json(result))
        assert doc["ok"] is True
        assert doc["files"] == result.files
        assert all(f["suppressed"] for f in doc["suppressed"])


# ---------------------------------------------------------------------------
# 6. Family G — cross-file resolution, in-tree exemplars, cache contract
# ---------------------------------------------------------------------------


def _tmp_pkg(tmp_path, files):
    """A throwaway package directory for genuine multi-file flow tests
    (the single-file fixture twins cannot exercise import resolution)."""
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    for name, src in files.items():
        (pkg / name).write_text(src)
    return str(pkg)


class TestFlowCrossFile:
    """Family G judged over a real multi-file package via lint_paths:
    the helper and its caller live in different modules."""

    def test_blocking_helper_in_another_module(self, tmp_path):
        pkg = _tmp_pkg(tmp_path, {
            "io_helpers.py":
                "import time\n\n\ndef flush():\n    time.sleep(0.2)\n",
            "server.py": (
                "import threading\n\n"
                "from pkg.io_helpers import flush\n\n\n"
                "class Store:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n\n"
                "    def put(self, v):\n"
                "        with self._lock:\n"
                "            flush()\n"
            ),
        })
        res = lint_paths([pkg])
        assert [
            (f.rule_id, os.path.basename(f.path)) for f in res.findings
        ] == [("flow-blocking-under-lock", "server.py")]
        # the verdict names both source locations: the held lock at the
        # call site and the blocking call inside the helper's file
        assert "io_helpers" in res.findings[0].message
        assert "time.sleep" in res.findings[0].message

    def test_one_level_limit_is_the_contract(self, tmp_path):
        # helper -> inner -> sleep is TWO hops from the lock: out of
        # contract by design (docs/lint.md#family-g) — must not fire
        pkg = _tmp_pkg(tmp_path, {
            "deep.py": (
                "import time\n\n\n"
                "def inner():\n    time.sleep(0.2)\n\n\n"
                "def helper():\n    return inner()\n"
            ),
            "server.py": (
                "import threading\n\n"
                "from pkg.deep import helper\n\n\n"
                "class Store:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n\n"
                "    def put(self, v):\n"
                "        with self._lock:\n"
                "            helper()\n"
            ),
        })
        assert lint_paths([pkg]).findings == []

    def test_deadline_dropped_across_modules(self, tmp_path):
        pkg = _tmp_pkg(tmp_path, {
            "store.py": (
                "def read_rows(shard, deadline=None):\n"
                "    return shard.read(deadline=deadline)\n"
            ),
            "router.py": (
                "from pkg.store import read_rows\n\n\n"
                "def fan_out(shards, deadline):\n"
                "    return [read_rows(s) for s in shards]\n"
            ),
        })
        res = lint_paths([pkg])
        assert [
            (f.rule_id, os.path.basename(f.path)) for f in res.findings
        ] == [("flow-deadline-dropped", "router.py")]

    def test_mapped_body_in_another_module(self, tmp_path):
        pkg = _tmp_pkg(tmp_path, {
            "bodies.py":
                "import jax\n\n\ndef gram(x):\n    return jax.lax.psum(x)\n",
            "train.py": (
                "from jax import shard_map\n\n"
                "from pkg import bodies\n\n\n"
                "def fit(mesh, x):\n"
                "    f = shard_map(bodies.gram, mesh=mesh,\n"
                "                  in_specs=None, out_specs=None)\n"
                "    return f(x)\n"
            ),
        })
        res = lint_paths([pkg])
        assert [
            (f.rule_id, os.path.basename(f.path)) for f in res.findings
        ] == [("spmd-collective-missing-axis", "train.py")]

    def test_thread_leak_stop_resolved_through_base_class(self, tmp_path):
        sub_src = (
            "import threading\n\n"
            "from pkg.base import StoppableBase\n\n\n"
            "class Ticker(StoppableBase):\n"
            "    def __init__(self):\n"
            "        self._worker = threading.Thread(target=self._run)\n"
            "        self._worker.start()\n\n"
            "    def _run(self):\n"
            "        pass\n"
        )
        pkg = _tmp_pkg(tmp_path, {
            "base.py": (
                "class StoppableBase:\n"
                "    def close(self):\n"
                "        self._worker.join(timeout=5)\n"
            ),
            "sub.py": sub_src,
        })
        # the join lives in the in-package base class: clean
        assert lint_paths([pkg]).findings == []
        # sever the base and the same class leaks
        (tmp_path / "pkg" / "sub.py").write_text(
            sub_src.replace("(StoppableBase)", "")
        )
        res = lint_paths([pkg])
        assert [f.rule_id for f in res.findings] == ["flow-thread-leak"]


class TestFlowExemplars:
    """In-tree clean exemplars for each flow-* rule, pinned from the
    shared package sweep: the classes that got the discipline right by
    review stay the executable documentation of it."""

    @pytest.mark.parametrize(
        "path_suffix,rule",
        [
            ("fleet/router.py", "flow-blocking-under-lock"),
            ("fleet/router.py", "flow-thread-leak"),
            ("workflow/batching.py", "flow-thread-leak"),
            ("obs/slo.py", "flow-thread-leak"),
            ("storage/remote.py", "flow-deadline-dropped"),
        ],
    )
    def test_in_tree_exemplar_is_clean(
        self, package_result, path_suffix, rule
    ):
        findings = _package_findings(package_result, path_suffix, rule)
        assert findings == [], (
            f"{path_suffix} regressed its {rule} exemplar status: "
            f"{[(f.rule_id, f.line) for f in findings]}"
        )

    def test_thread_leak_genuinely_engages_on_the_replica_tailer(self):
        """Strip the tailer's stop-Event set and the rule must fire:
        the real class is inside the rule's scope, not skipped."""
        path = os.path.join(
            REPO, "predictionio_tpu", "storage", "replica.py"
        )
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        assert "self._stop_polling.set()" in src  # the evidence the pin rides on
        mutated = src.replace("self._stop_polling.set()", "pass")
        findings = [
            f for f in lint_file(path, source=mutated)
            if f.rule_id == "flow-thread-leak" and not f.suppressed
        ]
        assert len(findings) == 1, (
            f"expected the de-evidenced tailer to fire exactly once, "
            f"got {[(f.rule_id, f.line) for f in findings]}"
        )


class TestLintCache:
    """The incremental-cache contract (docs/lint.md failure-mode table):
    warm is byte-identical to cold, invalidation is exactly the
    reverse-import closure for flow-* and the file itself for per-file
    families, a rules change invalidates the world, and a corrupt cache
    is simply a cold sweep — a stale cache can never suppress a
    finding."""

    A = "import time\n\n\ndef pause():\n    time.sleep(0.01)\n"
    B = (
        "import threading\n\n"
        "from pkg.a import pause\n\n\n"
        "class Gate:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n\n"
        "    def wait(self):\n"
        "        with self._lock:\n"
        "            pause()\n"
    )
    C = "def free():\n    return 1\n"

    def _pkg(self, tmp_path):
        return _tmp_pkg(
            tmp_path, {"a.py": self.A, "b.py": self.B, "c.py": self.C}
        )

    def _sweep(self, pkg, cache):
        return lint_paths([pkg], cache_path=str(cache))

    def test_warm_run_is_byte_identical_and_fully_cached(self, tmp_path):
        pkg = self._pkg(tmp_path)
        cache = tmp_path / "cache.json"
        cold = self._sweep(pkg, cache)
        warm = self._sweep(pkg, cache)
        # the cross-file finding exists AND survives cache round-trip
        assert [f.rule_id for f in cold.findings] == [
            "flow-blocking-under-lock"
        ]
        assert render_json(cold) == render_json(warm)
        assert cold.stats["cache_hits"] == 0
        assert len(cold.stats["parsed"]) == 3
        assert warm.stats["cache_hits"] == 3
        assert warm.stats["parsed"] == []
        assert warm.stats["flow_ran"] == []
        assert warm.stats["flow_cached"] == 3

    def test_edit_relints_exactly_the_reverse_import_closure(
        self, tmp_path
    ):
        pkg = self._pkg(tmp_path)
        cache = tmp_path / "cache.json"
        self._sweep(pkg, cache)
        (tmp_path / "pkg" / "a.py").write_text(
            self.A.replace("0.01", "0.02")
        )
        res = self._sweep(pkg, cache)
        parsed = [os.path.basename(p) for p in res.stats["parsed"]]
        flow_ran = [os.path.basename(p) for p in res.stats["flow_ran"]]
        # per-file families: only the edited file re-parses
        assert parsed == ["a.py"]
        # flow-*: the edited file plus its reverse importers; c.py's
        # flow verdict comes from cache untouched
        assert flow_ran == ["a.py", "b.py"]
        assert res.stats["flow_cached"] == 1
        assert [f.rule_id for f in res.findings] == [
            "flow-blocking-under-lock"
        ]

    def test_from_package_import_submodule_is_a_tracked_dep(
        self, tmp_path
    ):
        """``from pkg import a`` binds a submodule the resolver follows,
        so the cache's dependency set must cover it too: editing the
        helper into a blocker must surface the importer's new finding
        on the very next warm run — the resolver and the deps
        disagreeing here IS the stale-cache-suppresses-a-finding mode."""
        pkg = _tmp_pkg(tmp_path, {
            "__init__.py": "",
            "a.py": "def pause():\n    return 0\n",
            "b.py": (
                "import threading\n\n"
                "from pkg import a\n\n\n"
                "class Gate:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n\n"
                "    def wait(self):\n"
                "        with self._lock:\n"
                "            a.pause()\n"
            ),
        })
        cache = tmp_path / "cache.json"
        cold = self._sweep(pkg, cache)
        assert cold.findings == []
        (tmp_path / "pkg" / "a.py").write_text(
            "import time\n\n\ndef pause():\n    time.sleep(0.2)\n"
        )
        warm = self._sweep(pkg, cache)
        flow_ran = [os.path.basename(p) for p in warm.stats["flow_ran"]]
        assert "b.py" in flow_ran
        assert [f.rule_id for f in warm.findings] == [
            "flow-blocking-under-lock"
        ]
        assert warm.findings[0].path.endswith("b.py")

    def test_rules_version_bump_invalidates_everything(
        self, tmp_path, monkeypatch
    ):
        from predictionio_tpu.lint import engine

        pkg = self._pkg(tmp_path)
        cache = tmp_path / "cache.json"
        self._sweep(pkg, cache)
        monkeypatch.setattr(engine, "RULES_VERSION", "bumped-for-test")
        res = self._sweep(pkg, cache)
        assert res.stats["cache_hits"] == 0
        assert len(res.stats["parsed"]) == 3

    def test_corrupt_cache_falls_back_to_cold_sweep(self, tmp_path):
        pkg = self._pkg(tmp_path)
        cache = tmp_path / "cache.json"
        cold = self._sweep(pkg, cache)
        cache.write_text('{"version": 1, "files": [torn mid-write')
        res = self._sweep(pkg, cache)
        assert res.stats["cache_hits"] == 0
        assert render_json(res) == render_json(cold)  # verdict unchanged
        # and the torn file was atomically replaced with a good one
        assert self._sweep(pkg, cache).stats["cache_hits"] == 3

    def test_partial_rule_sets_never_touch_the_cache(self, tmp_path):
        # a --select run writing results a full run would later trust
        # IS the stale-cache-suppresses-a-finding failure mode
        pkg = self._pkg(tmp_path)
        cache = tmp_path / "cache.json"
        lint_paths(
            [pkg], select={"flow-blocking-under-lock"},
            cache_path=str(cache),
        )
        assert not cache.exists()


class TestExplainAndChangedClosure:
    """``pio lint --explain`` and the ``--changed`` reverse-import
    closure, in-process like TestChangedAndBaseline."""

    def _run(self, capsys, *argv):
        from predictionio_tpu.tools import lint as lint_cli

        rc = lint_cli.main(list(argv))
        return rc, capsys.readouterr().out

    def test_explain_prints_docstring_and_doc_anchor(self, capsys):
        rc, out = self._run(capsys, "--explain", "flow-thread-leak")
        assert rc == 0
        assert "docs/lint.md#flow-thread-leak" in out
        # a docstring phrase, not just the --list-rules short line
        assert "story reachable from" in out

    def test_explain_unknown_rule_is_an_engine_error(self, capsys):
        rc, out = self._run(capsys, "--explain", "no-such-rule")
        assert rc == 2
        assert "no-such-rule" in out

    def _git(self, cwd, *args):
        return subprocess.run(
            ["git", *args], cwd=cwd, capture_output=True, text=True,
            timeout=30,
        )

    def test_changed_pulls_in_reverse_import_closure(
        self, tmp_path, capsys, monkeypatch
    ):
        """Editing only the helper must re-judge its importer: the
        flow-* finding lands in a file git does NOT report changed."""
        repo = tmp_path / "repo"
        repo.mkdir()
        assert self._git(repo, "init", "-q").returncode == 0
        self._git(repo, "config", "user.email", "t@example.com")
        self._git(repo, "config", "user.name", "t")
        (repo / "a.py").write_text(
            "def pause():\n    return 0\n"
        )
        (repo / "b.py").write_text(
            "import threading\n\n"
            "from a import pause\n\n\n"
            "class Gate:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n\n"
            "    def wait(self):\n"
            "        with self._lock:\n"
            "            pause()\n"
        )
        self._git(repo, "add", "-A")
        assert self._git(repo, "commit", "-qm", "seed").returncode == 0
        monkeypatch.chdir(repo)
        # edit ONLY the helper: it now blocks
        (repo / "a.py").write_text(
            "import time\n\n\ndef pause():\n    time.sleep(0.2)\n"
        )
        rc, out = self._run(capsys, "--changed", str(repo))
        assert rc == 1, out
        assert "2 files" in out  # a.py (changed) + b.py (closure)
        assert "flow-blocking-under-lock" in out
        assert "b.py" in out
