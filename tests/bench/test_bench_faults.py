"""A run with the timed path broken underneath comes out not correct.

Each test drives ``run_cell`` (everything a benchmark run does after its
look for a chip) at a tiny size on the CPU, once sound and once for each
fault the cell can have.  Both cells run on one chip, so there is no
exchange between chips to leave out.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))  # the checkout root, for ``bench``

import numpy as np
import pytest

from bench import run

# At 60k points a cluster holds a few thousand, so one point that lands
# across a boundary in float32 moves a centroid by ~5e-3: the tiny
# k-means cell is held to 1e-2, not to the cell's limit at 20M points.
TINY = {"terasort-gray-10m": {"records": 20000, "chunk_bytes": 256 * 1024},
        "kmeans-hibench-session": {"samples": 60000,
                                   "chunk_bytes": 256 * 1024,
                                   "limits": {"centroid_err": 1e-2}}}
SEED = 2**31 + 99


def _run(cell):
    spec = run.resolve(cell)
    spec.config.update(TINY[cell])
    res = run.run_cell(spec, SEED, 0.3, False,
                       peaks=run.peaks_for("TPU v5 lite"))
    return run.result_line(spec, res, False,
                           {"platform": "cpu", "kind": "cpu", "count": 1})


def _half_records(blob: bytes, width: int) -> bytes:
    n = len(blob) // width
    return blob[:(n // 2) * width]


def _flip_first_byte(outputs):
    first = bytearray(outputs[0])
    first[0] ^= 0xFF
    return [bytes(first)] + list(outputs[1:])


TERASORT_FAULTS = {
    # the sort stage hands back its input unchanged
    "state_unchanged": lambda mp: mp.setattr(
        "repro.core.records.RecordBatch.sort_by_key",
        lambda self, key_bytes: self.compact()),
    # half of every bucket's records left out of the output
    "half_left_out": lambda mp: _wrap_outputs(
        mp, lambda outs: [_half_records(b, 100) for b in outs]),
    # one output byte altered where the output is produced
    "answer_altered": lambda mp: _wrap_outputs(mp, _flip_first_byte),
}


def _wrap_outputs(mp, change):
    from repro.core.executor import ArrayExecutor

    orig = ArrayExecutor.outputs
    mp.setattr(ArrayExecutor, "outputs",
               lambda self, parts: change(orig(self, parts)))


def _fold_unchanged(outputs, dim, k, backend):
    return np.zeros((k, dim)), np.zeros(k)


def _assign_half(x, c, valid=None, **kw):
    import jax.numpy as jnp
    from repro.kernels.kmeans_assign import kmeans_assign_partials

    half = jnp.arange(x.shape[0]) < x.shape[0] // 2
    valid = half if valid is None else valid & half
    return kmeans_assign_partials(x, c, valid, **kw)


def _fold_altered(orig):
    def fold(outputs, dim, k, backend):
        sums, counts = orig(outputs, dim, k, backend)
        sums = sums.copy()
        sums[0, 0] += 0.05 * counts[0]
        return sums, counts
    return fold


def _kmeans_fault(name, mp):
    import repro.core.kmeans as km

    if name == "state_unchanged":      # no centroid update ever lands
        mp.setattr(km, "_fold_outputs", _fold_unchanged)
    elif name == "half_left_out":      # each chunk's second half skipped
        mp.setattr(km, "kmeans_assign_partials", _assign_half)
    else:                              # a fold result altered by 0.05
        mp.setattr(km, "_fold_outputs", _fold_altered(km._fold_outputs))


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(TERASORT_FAULTS))
def test_terasort_fault_is_not_correct(fault, monkeypatch):
    TERASORT_FAULTS[fault](monkeypatch)
    line = _run("terasort-gray-10m")
    assert not line["correct"]
    assert line["checks"]["misplaced_records"]["value"] > 0
    assert line["failed"] == line["attempted"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_kmeans_fault_is_not_correct(fault, monkeypatch):
    _kmeans_fault(fault, monkeypatch)
    line = _run("kmeans-hibench-session")
    assert not line["correct"]
    c = line["checks"]["centroid_err"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_fewer_replicas_is_not_correct(cell, monkeypatch):
    """The upload stores one replica where the configuration asks for 3."""
    from repro.sector import SectorMaster

    orig = SectorMaster.placement
    monkeypatch.setattr(SectorMaster, "placement",
                        lambda self, *a, **kw: orig(self, *a, **kw)[:1])
    line = _run(cell)
    assert not line["correct"]
    assert line["checks"]["under_replicated_chunks"]["value"] > 0
