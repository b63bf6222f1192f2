"""``correct`` of ``kmeans_mnist8m_full.fit`` has been shown to fail, at a
size a test run can hold (on the chip at the cell's own size: the
configuration's ``limits_notes``, PERF.md section 2): against
``references/lloyd_kmeans_u8_blocks.py`` the control (the plain reference
with the operands of both contractions rounded to fp8, put in the
program's place) and the faults "unchanged", "every second row" and "no
exchange between the chips" read over a limit, the reference itself and a
sound ``KMeans.fit`` do not (the timed path broken underneath ``run.py``
is ``test_kmeans_mnist8m_correct.py``'s, ``fit``'s session, which this
cell runs; tier-1 rehearses this cell).  The reference imports nothing of
the program, refuses rows that are not whole grey levels, and gives the
numbers of ``lloyd_kmeans_blocks.py`` (the same algorithm over float32
blocks) on the same data."""

import ast
import os

import numpy as np
import pytest

from conftest import HERE
from harness import files

CELL = "kmeans_mnist8m_full.fit"
SEED = 2147483693
# sizes for the control and the faults: enough centroids, rounds and shapes
# for the lower precision to show (as test_kmeans_mnist8m_correct.py)
SIZES = {"rows": 32768, "k": 256, "max_iter": 20,
         "generator_params": {"prototypes": 400},
         "reference_params": {"k": 256, "iterations": 20, "block": 4096}}


@pytest.fixture(scope="module")
def cell():
    _, config = files.cell(CELL, rehearsal=True)
    config = files.overlaid(config, SIZES)
    reference = files.module("references", config["reference"])
    return config, reference, files.generate(config, SEED)


def over_limits(config, numbers):
    return [n for n, limit in config["limits"].items()
            if not numbers[n] <= limit]


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(HERE, "references", "lloyd_kmeans_u8_blocks.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    modules = {node.module if isinstance(node, ast.ImportFrom)
               else alias.name
               for node in ast.walk(tree)
               if isinstance(node, (ast.Import, ast.ImportFrom))
               for alias in node.names}
    assert not [m for m in modules if m and m.startswith("flink_ml_tpu")]
    assert "references.lloyd_kmeans" in modules


def test_the_reference_itself_is_within_every_limit(cell):
    config, reference, data = cell
    own = reference.control(config, data, SEED,
                            config["reference_params"]["operand_dtype"])
    numbers = reference.compare(config, data, own, SEED)
    assert set(config["limits"]) <= set(numbers)
    assert {numbers[n] for n in config["limits"]} == {0.0}


@pytest.mark.parametrize("kind", ["control", "unchanged", "half_batch",
                                  "no_exchange"])
def test_control_and_faults_are_over_a_limit(cell, kind):
    config, reference, data = cell
    stand_in = (reference.control(config, data, SEED) if kind == "control"
                else reference.fault(config, data, SEED, kind))
    assert over_limits(config, reference.compare(config, data, stand_in,
                                                 SEED)), kind


def test_no_exchange_is_the_first_chips_rows_alone(cell):
    config, reference, data = cell
    quarter = {"features": data["features"][:config["rows"] // 4]}
    one = files.overlaid(config, {"reference_params": {"iterations": 1}})
    got = reference.fault(one, data, SEED, "no_exchange")["centroids"]
    # the same start: the seed's rows of the WHOLE table
    start = reference.initial_centroids(data["features"],
                                        config["reference_params"]["k"], SEED)
    alone = lloyd_step(quarter["features"], start)
    assert np.array_equal(got.astype(np.float32), alone)


def lloyd_step(points, start):
    """One pass in numpy, float64 scores (whole levels: exact)."""
    p, c = points.astype(np.float64), start.astype(np.float64)
    scores = np.sum(c * c, axis=1)[None] - 2.0 * p @ c.T
    nearest = np.argmin(scores, axis=1)
    out = start.astype(np.float32).copy()
    for j in np.unique(nearest):
        mine = points[nearest == j]
        out[j] = (np.sum(mine, axis=0, dtype=np.float64).astype(np.float32)
                  / np.float32(len(mine)))
    return out


def test_sound_fit_is_within_the_limits_at_these_sizes(cell):
    """``KMeans.fit`` through the cell's runner against the reference:
    inside every limit."""
    import jax

    config, reference, data = cell
    workload, _ = files.cell(CELL, rehearsal=True)
    session = files.module("runners", workload["runner"]).prepare(
        config, data, SEED, jax.devices()[:4])
    answer = session.answer(session.call())
    numbers = reference.compare(config, data, answer, SEED)
    assert not over_limits(config, numbers), numbers


def test_the_uint8_reference_gives_the_float32_references_numbers(cell):
    """The same Lloyd over ``uint8`` blocks a device and over one upload
    of float32 blocks: the whole levels make the sums exact on both sides,
    so their centroids agree to the last bits of a score."""
    config, reference, data = cell
    blocks = files.module("references", "lloyd_kmeans_blocks")
    mine = reference.control(config, data, SEED, "bfloat16")["centroids"]
    theirs = blocks.control(config, data, SEED, "bfloat16")["centroids"]
    gaps = np.sqrt(np.sum((mine - theirs) ** 2, axis=1))
    assert np.median(gaps) <= 1e-3 * np.sqrt(np.mean(np.sum(theirs ** 2,
                                                            axis=1)))


def test_rows_that_are_not_whole_levels_are_refused(cell):
    config, reference, data = cell
    half = {"features": data["features"][:512] + np.float32(0.5)}
    with pytest.raises(ValueError, match="whole levels"):
        reference.control(config, half, SEED)
    over = {"features": data["features"][:512] + np.float32(256.0)}
    with pytest.raises(ValueError, match="whole levels"):
        reference.control(config, over, SEED)
    held = reference.levels(data["features"][:1000], 256)
    assert held.dtype == np.uint8 and held.shape == (4, 256, 784)
    assert np.array_equal(held.reshape(-1, 784)[:1000], data["features"][:1000])
    assert not held.reshape(-1, 784)[1000:].any()


def test_the_counts_are_a_chips_share_of_the_iteration(cell):
    """``step_counts`` of the four-chip configuration are the one-chip
    configuration's at its quarter of the rows: the readers divide by ONE
    chip's peaks, so a share over the whole job would read up to 400%."""
    full = files.load_json("configs", "kmeans_mnist8m_full.json")
    quarter = files.load_json("configs", "kmeans_mnist8m.json")
    assert full["rows"] == 4 * quarter["rows"] == full["published"]["rows"]
    assert full["reduced"] == [] and full["chips"] == 4
    mine = files.module("configs", full["counts"])
    theirs = files.module("configs", quarter["counts"])
    assert mine.step_counts(full) == theirs.step_counts(quarter)
    assert mine.kernel_counts(full) == theirs.kernel_counts(quarter)
    assert mine.step_counts(full)["flops"] / 197e12 == pytest.approx(
        0.0660, rel=2e-3)
