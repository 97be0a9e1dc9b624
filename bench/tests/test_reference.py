"""The plain reference against the program, at a small size on the CPU.

Held-out scores of the program's models, and of the reference's, through the
reference's float32 forward pass: a one-shot fit, a vmap fleet fit with one
seed per tenant, and a federation of the fleet's tenants by the tree merge
on four virtual devices.  On the CPU both sides compute in float32 in the
same order of operations up to summation, so the gaps sit near 1e-6.
"""
import jax
import numpy as np
import pytest

import check
import reference
import synth

SIZES = (12, 4, 6, 8, 12)
ARCH = reference.Arch(SIZES, 0.8, 0.9)
SHAPE = synth.Shape.from_table(12, 900, 60)
TOL = 1e-4


@pytest.fixture(scope="module")
def data():
    train, test = synth.replicas(5, SHAPE, 8)
    return train, test


def _engine(**plan):
    from repro.core import daef
    from repro.engine import DAEFEngine, ExecutionPlan

    cfg = daef.DAEFConfig(layer_sizes=SIZES, lam_hidden=0.8, lam_last=0.9)
    return DAEFEngine(cfg, ExecutionPlan(**plan))


def _gap(model, ref, x):
    return check.rel_gap(reference.scores(model, x), reference.scores(ref, x))


def test_one_shot_fit_matches_the_reference(data):
    train, test = data
    model = _engine().fit(train[0])
    assert _gap(model, reference.fit(ARCH, train[0], 0), test[0]) < TOL


def test_fleet_fit_matches_the_reference_per_tenant(data):
    train, test = data
    fleet = _engine(mode="vmap", tenants=8).fit(train, seeds=np.arange(8))
    for t in (0, 5, 7):
        ws = tuple(w[t] for w in fleet.model.weights)
        bs = tuple(b[t] for b in fleet.model.biases)
        got = reference.Model(ws, bs)
        assert _gap(got, reference.fit(ARCH, train[t], t), test[t]) < TOL


def test_tree_merge_on_four_devices_matches_the_reference_federation(data):
    assert len(jax.devices()) == 4
    train, test = data
    engine = _engine(mode="mesh", tenants=8, mesh_devices=4, merge="tree")
    merged = engine.reduce(engine.fit(train, seeds=np.zeros(8, np.int32)), group_size=8)
    got = reference.Model(tuple(w[0] for w in merged.model.weights),
                          tuple(b[0] for b in merged.model.biases))
    held_out = np.concatenate(list(test), axis=1)
    assert _gap(got, reference.federate(ARCH, train, 0), held_out) < TOL


def test_the_federation_is_not_the_pooled_fit(data):
    """Each site's decoder statistics come from its own encoder, so the
    federated model differs from a fit on the pooled data (daef.merge_models):
    the check compares the federation with the reference's federation."""
    train, test = data
    pooled = reference.fit(ARCH, np.concatenate(list(train), axis=1), 0)
    held_out = np.concatenate(list(test), axis=1)
    assert _gap(reference.federate(ARCH, train, 0), pooled, held_out) > 10 * TOL

