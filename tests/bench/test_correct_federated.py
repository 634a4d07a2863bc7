"""`correct` for the federated cells at a size the CPU holds: the program
passes the cell's limits, while each fault a round can have, planted in
the timed path, and the cell's control (the reference in bfloat16 in the
program's place) fail them. The harness's look for a chip is skipped;
everything else runs as in a run of the cell.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import common

FED_CELLS = ("vitb-fed-u10", "vitb-fed-u1-secure")


def _freeze_state(engine):
    """A round that returns its state unchanged."""
    trainer = engine.trainer
    real = trainer.round

    def frozen(state, data, participation=None, init_tails=None):
        return state, real(state, data, participation, init_tails)[1]
    trainer.round = frozen


def _half_batch(monkeypatch):
    """The loss over the first half of each batch only."""
    from repro.core import losses
    real = losses.classifier_loss

    def half(logits, labels, *, impl="auto"):
        n = logits.shape[0] // 2
        return real(logits[:n], labels[:n], impl=impl)
    monkeypatch.setattr(losses, "classifier_loss", half)


@pytest.mark.parametrize("fault", [None, "unchanged_state", "half_batch"])
@pytest.mark.parametrize("cell", FED_CELLS)
def test_federated_round_faults_fail(cell, fault, monkeypatch):
    plant = _freeze_state if fault == "unchanged_state" else None
    if fault == "half_batch":
        _half_batch(monkeypatch)
    mix = tiny.rounds(cell)
    out = tiny.run(tiny.VIT, mix, "vit-base", cell, plant=plant)
    assert tiny.correct(out) == (fault is None), out["checks"]


@pytest.mark.parametrize("cell", FED_CELLS)
def test_federated_unequal_client_sizes_pass(cell):
    """Clients of 8, 5, 3 and 2 true samples: the program's FedAvg weights
    them by true size, and so does the reference."""
    mix = dict(tiny.rounds(cell), client_sizes=[8, 5, 3, 2])
    out = tiny.run(tiny.VIT, mix, "vit-base", cell)
    assert tiny.correct(out), out["checks"]


@pytest.mark.parametrize("cell", FED_CELLS)
def test_federated_control_fails(cell):
    """The reference computed in bfloat16, in the program's place."""
    from bench.systems import federated as fed
    mix = tiny.rounds(cell)
    r = common.Run(config=tiny.VIT, traffic=mix,
                   ref=common.load_module(common.BENCH / "configs"
                                          / "vit-base.py"),
                   limits=tiny.limits(cell), seed=11, seconds=0)
    keep = fed.keep_count(mix["samples_per_client"],
                          tiny.VIT["split"]["prune_gamma"], mix["batch"])
    cohorts = [np.array([0, 1]), np.array([2, 3])]
    ref_snaps, ref_losses, ref_wire = fed.reference_rounds(r, cohorts, keep)
    snaps, losses, _ = fed.reference_rounds(r, cohorts, keep,
                                            dtype=jnp.bfloat16)
    wire = {f"wire/{k}_bytes": v for k, v in ref_wire.items()}
    got = fed.readings(snaps, [dict(l, **wire) for l in losses], ref_snaps,
                       ref_losses, ref_wire)
    assert any(got[k] > r.limits[k]["limit"] for k in
               ("loss", "update", "change")), got
