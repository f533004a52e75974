"""``chip_smoke.py`` off the chip: it parses, imports without touching a
JAX backend, refuses to carry on without a TPU, and its phases run end to
end at a tiny size on the CPU backend (the rehearsal the on-chip run is
prepared with — same entry points, same checks, the CPU's own levers)."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Workload(
    n_users=600, n_items=200, n_ratings=8_000, rank=50, iterations=3,
    single_queries=4, burst=8,
)
CPU = chip_smoke.Expect(platform="cpu", solve_mode="chunked",
                        fused_gather=False)


def test_help_parses(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.build_parser().parse_args(["--help"])
    assert exc.value.code == 0
    assert "--chips" in capsys.readouterr().out
    assert chip_smoke.build_parser().parse_args([]).chips == 1
    assert chip_smoke.build_parser().parse_args(["--chips", "4"]).chips == 4


def test_phase_list_imports_without_a_backend():
    """Importing the script (its phase lists included) neither imports
    jax nor anything of the package: the parent must never hold the chip."""
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke as cs; "
        "assert [p.__name__ for p in cs.PHASES_ONE_CHIP] == "
        "['phase_device', 'phase_import', 'phase_train', 'phase_serve', "
        "'phase_undeploy'], cs.PHASES_ONE_CHIP; "
        "assert [p.__name__ for p in cs.PHASES_SHARDED] == "
        "['phase_device', 'phase_import', 'phase_sharded_compare']; "
        "assert 'jax' not in sys.modules; "
        "assert not any(m.startswith('predictionio_tpu') for m in sys.modules)"
        % REPO
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_refuses_to_carry_on_without_a_tpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero, a message
    that names the platform found, and no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "JAX found platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
    # it stopped at the first phase: nothing was imported or trained
    assert "import:" not in proc.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert "bin/pio is missing" in proc.stderr and proc.stdout == ""


def test_synth_ratings_hold_full_width():
    users, items, ratings = chip_smoke.synth_ratings(TINY, seed=0)
    assert len(ratings) == TINY.n_ratings
    # every user and every item is rated, so the trained tables have
    # exactly n_users x n_items rows whatever the cut in ratings
    assert len(np.unique(users)) == TINY.n_users
    assert len(np.unique(items)) == TINY.n_items
    again = chip_smoke.synth_ratings(TINY, seed=0)
    assert all(np.array_equal(a, b) for a, b in
               zip((users, items, ratings), again))
    assert not np.array_equal(chip_smoke.synth_ratings(TINY, seed=1)[0], users)


def _run_with_factors(uf, itf):
    run = chip_smoke.Run(chip_smoke.Workload(num=3), CPU, chips=1, seed=0)

    class Model:
        user_map = {f"u{i}": i for i in range(len(uf))}
        item_map = {f"i{i}": i for i in range(len(itf))}

    run.model, run.factors = Model, (uf, itf)
    return run


def test_check_answer_accepts_only_ties_within_1e_4():
    uf = np.array([[1.0]], np.float32)
    itf = np.array([[0.9], [0.5], [0.50005], [0.1]], np.float32)
    run = _run_with_factors(uf, itf)

    def answer(*rows):
        return {"itemScores": [
            {"item": f"i{r}", "score": float(itf[r, 0])} for r in rows]}

    chip_smoke.check_answer(run, 0, answer(0, 2, 1))  # numpy's own order
    chip_smoke.check_answer(run, 0, answer(0, 1, 2))  # a 5e-5 tie, swapped
    with pytest.raises(chip_smoke.SmokeFailure, match="numpy"):
        chip_smoke.check_answer(run, 0, answer(0, 2, 3))  # a wrong item
    with pytest.raises(chip_smoke.SmokeFailure, match="served twice"):
        chip_smoke.check_answer(run, 0, answer(0, 2, 2))
    with pytest.raises(chip_smoke.SmokeFailure, match="2 items served"):
        chip_smoke.check_answer(run, 0, answer(0, 2))


def test_one_chip_phases_end_to_end_on_the_cpu(capfd):
    """import -> train -> deploy --spawn -> 12 queries -> undeploy through
    bin/pio, every answer checked against numpy, no process left."""
    result = chip_smoke.run_smoke(TINY, CPU, chips=1, seed=0)
    assert result["ok"] is True and result["device"]["platform"] == "cpu"
    out = capfd.readouterr().out
    for needle in (
        "rank 50, 600 users x 200 items",
        "reduced: ratings 8000/20000263 (import rate)",
        "solve_mode: chunked", "fused_gather: false",
        "12/12 answers equal numpy", "compile cache ",
        "is gone after",
    ):
        assert needle in out, out


def test_sharded_phases_end_to_end_on_virtual_devices(capfd):
    """The ``--chips 4`` path on the CPU's virtual devices: --shards 1
    against --shards 4, factors allclose, shards on 4 distinct devices."""
    result = chip_smoke.run_smoke(TINY, CPU, chips=4, seed=0)
    assert result["device"]["count"] >= 4
    out = capfd.readouterr().out
    assert '"user": [0, 1, 2, 3], "item": [0, 1, 2, 3]' in out, out
    assert "train-shards-4: solve_mode: chunked" in out
    assert "deploy" not in out  # no serving phase on this path
