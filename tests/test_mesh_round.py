"""Mega-cohort rounds on the device mesh: sharded cohort dispatch is
bit-comparable to the single-device vmap round (clear AND secure, with
dropouts), the frozen body stays UNBATCHED in the compiled HLO, and
hierarchical (client -> edge -> global) aggregation matches flat FedAvg
plus a two-tier metered wire breakdown.

The multi-device tests need >= 8 visible devices — run the suite under
XLA_FLAGS=--xla_force_host_platform_device_count=8 (CI's test-mesh8 job);
on the default 1-device run they skip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import ProtocolConfig, SFPromptTrainer, SplitConfig, SplitModel
from repro.core.aggregation import (broadcast_to_clients, fedavg_partial,
                                    get_aggregator, hierarchical_fedavg)
from repro.core.comm import (hierarchical_edge_breakdown,
                             hierarchical_secure_agg_breakdown)
import repro.fed.population as population_mod
from repro.data import (DATASETS, synthetic_image_dataset,
                        synthetic_lm_dataset)
from repro.fed import (ClientSampler, EdgeTopology, FederatedEngine,
                       HierarchicalAggregator, Population, RoundScheduler,
                       StragglerConfig)
from repro.launch.mesh import make_host_mesh
from repro.privacy.fixed_point import roundtrip_tol
from repro.sharding import cohort_pspecs, params_pspecs

KEY = jax.random.PRNGKey(0)
N_LOCAL = 4
BATCH = 4

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="sharded-cohort tests need 8 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def setup():
    # distinctive dims (32 / 48) so HLO shape strings are unambiguous
    cfg = get_config("vit-base").reduced(n_layers=3, d_model=32, d_ff=48)
    split = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=2,
                        prune_gamma=0.5, local_epochs=1)
    return cfg, split


def make_trainer(cfg, split, *, k, aggregator=None, mesh=None):
    model = SplitModel(cfg, split)
    pcfg = ProtocolConfig(clients_per_round=k, local_epochs=1,
                          batch_size=BATCH, momentum=0.0)
    return SFPromptTrainer(model, pcfg, aggregator, mesh=mesh)


def cohort_batch(k, *, seed=0):
    data = synthetic_image_dataset(DATASETS["cifar10-syn"], k * N_LOCAL,
                                   seed=seed, image_hw=32)
    return {name: jnp.asarray(v).reshape((k, N_LOCAL) + v.shape[1:])
            for name, v in data.items()}


def dropout_participation(k, *, n_dropped, n_late=0):
    transmit = np.ones(k, np.float32)
    aggregate = np.ones(k, np.float32)
    aggregate[:n_dropped] = 0.0
    transmit[:n_dropped] = 0.0
    transmit[n_dropped:n_dropped + n_late] = 0.4
    return {"transmit": jnp.asarray(transmit),
            "aggregate": jnp.asarray(aggregate)}


def trainable_nbytes(params):
    tr = {"tail": params["tail"], "prompt": params["prompt"]}
    return float(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tr)))


def random_cohort_tree(key, k):
    return {"tail": {"w": jax.random.normal(key, (k, 7, 3)),
                     "b": jax.random.normal(jax.random.fold_in(key, 1),
                                            (k, 5))},
            "prompt": jax.random.normal(jax.random.fold_in(key, 2),
                                        (k, 4, 8))}


# ------------------------------------------------------------- guardrails
def test_broadcast_to_clients_rejects_nonpositive_k():
    """Regression: k <= 0 must fail HERE with the cohort size in the
    message, not later as an opaque zero-length vmap axis error."""
    tree = {"w": jnp.ones((3, 2))}
    for bad in (0, -4):
        with pytest.raises(ValueError, match="cohort"):
            broadcast_to_clients(tree, bad)
    out = broadcast_to_clients(tree, 2)
    assert out["w"].shape == (2, 3, 2)


def test_edge_topology_validation():
    with pytest.raises(ValueError, match="positive"):
        EdgeTopology(0, 1)
    with pytest.raises(ValueError, match="more edges"):
        EdgeTopology(4, 8)
    with pytest.raises(ValueError, match="not divisible"):
        EdgeTopology(10, 4)
    topo = EdgeTopology(8, 2)
    assert topo.edge_size == 4
    np.testing.assert_array_equal(topo.assignment,
                                  [0, 0, 0, 0, 1, 1, 1, 1])
    assert topo.members(1) == slice(4, 8)


def test_pspecs_on_data_only_host_mesh():
    """Regression: rule tables mention 'model', but a host mesh has only
    'data' — mesh-absent axes must drop instead of KeyError-ing, and the
    cohort leading axis must land on the client plane."""
    mesh = make_host_mesh()
    params = {"body": {"w": jnp.zeros((32, 48))},
              "tail": {"w": jnp.zeros((32, 10))}}
    specs = params_pspecs(params, mesh)
    assert all(isinstance(s, P) for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))
    k = jax.device_count()
    cohort = {"x": jnp.zeros((k, 5)), "flag": jnp.zeros((k,))}
    cspecs = cohort_pspecs(cohort, mesh)
    assert cspecs["x"] == P("data", None)
    assert cspecs["flag"] == P("data")
    # a K that does not divide the device count replicates, never fails
    # (vacuous on a 1-device mesh — everything divides 1)
    if jax.device_count() > 1:
        odd = cohort_pspecs(
            {"x": jnp.zeros((jax.device_count() * 2 + 1, 3))}, mesh)
        assert odd["x"] == P(None, None)


def test_make_host_mesh_rejects_oversubscription():
    with pytest.raises(ValueError, match="device"):
        make_host_mesh(jax.device_count() + 1)


# -------------------------------------------------- hierarchical == flat
@pytest.mark.parametrize("weights", [
    [3.0, 2.0, 7.0, 1.0, 5.0, 4.0],       # full participation
    [3.0, 0.0, 7.0, 1.0, 0.0, 4.0],       # dropouts across edges
    [0.0, 0.0, 7.0, 1.0, 5.0, 4.0],       # edge 0 entirely dropped
])
def test_hierarchical_fedavg_matches_flat(weights):
    """Two-tier survivor-weighted mean == flat fedavg_partial up to float
    reassociation, including when a whole edge drops (W_e = 0)."""
    k = len(weights)
    tree = random_cohort_tree(KEY, k)
    w = jnp.asarray(weights)
    fb = jax.tree.map(lambda x: jnp.ones_like(x[0]), tree)
    topo = EdgeTopology(k, 3)
    hier = hierarchical_fedavg(tree, w, fb, jnp.asarray(topo.assignment), 3)
    flat = fedavg_partial(tree, w, fb)
    for a, b in zip(jax.tree.leaves(hier), jax.tree.leaves(flat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_hierarchical_fedavg_all_dropped_falls_back():
    tree = random_cohort_tree(KEY, 4)
    fb = jax.tree.map(lambda x: jnp.full_like(x[0], 3.25), tree)
    out = hierarchical_fedavg(tree, jnp.zeros((4,)), fb,
                              jnp.asarray(EdgeTopology(4, 2).assignment), 2)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(fb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hierarchical_clear_round_matches_flat(setup):
    """A full protocol round through the edge topology lands on the flat
    round's params, and the edge_global stream meters exactly
    (E + live_edges) * param_bytes."""
    cfg, split = setup
    k, n_edges = 4, 2
    data = cohort_batch(k)
    part = dropout_participation(k, n_dropped=1)   # edge 0 keeps 1 client

    flat = make_trainer(cfg, split, k=k)
    st_f, m_f = flat.round(flat.init(KEY), data, dict(part))
    hier = make_trainer(cfg, split, k=k,
                        aggregator=get_aggregator(n_edges=n_edges,
                                                  cohort_size=k))
    st_h, m_h = hier.round(hier.init(KEY), data, dict(part))

    for a, b in zip(jax.tree.leaves(st_f["params"]),
                    jax.tree.leaves(st_h["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=1e-7)
    # phase-2 smashed traffic identical; phase-3 uplink accounting too
    # (clear edges keep the seed-exact (K + survivors) * param_bytes)
    assert m_f["wire/head_body_bytes"] == m_h["wire/head_body_bytes"]
    assert m_f["wire/params_bytes"] == m_h["wire/params_bytes"]
    pb = trainable_nbytes(st_h["params"])
    live_edges = 2.0   # the dropout left a survivor on both edges
    expect = hierarchical_edge_breakdown(param_nbytes=pb, n_edges=n_edges,
                                         live_edges=live_edges)
    assert m_h["wire/edge_global_bytes"] == expect["edge_global"]
    assert hier.meter.totals["edge_global"] == expect["edge_global"]
    assert "wire/edge_global_bytes" not in m_f


def test_hierarchical_secure_round_matches_clear(setup):
    """Per-edge masked aggregation composes with the topology: the secure
    hierarchical round matches the clear hierarchical round within
    fixed-point tolerance, and the metered two-tier bytes match the
    analytical breakdown within 5% — under a straggler plan."""
    cfg, split = setup
    k, n_edges = 4, 2
    data = cohort_batch(k)
    part = dropout_participation(k, n_dropped=1, n_late=1)

    clear = make_trainer(cfg, split, k=k,
                         aggregator=get_aggregator(n_edges=n_edges,
                                                   cohort_size=k))
    st_c, _ = clear.round(clear.init(KEY), data, dict(part))
    sec = make_trainer(
        cfg, split, k=k,
        aggregator=get_aggregator(secure=True, n_edges=n_edges,
                                  cohort_size=k, impl="ref", seed=3))
    st_s, m_s = sec.round(sec.init(KEY), data, dict(part))

    tol = roundtrip_tol(k)
    for a, b in zip(jax.tree.leaves(st_c["params"]),
                    jax.tree.leaves(st_s["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol)

    params = st_s["params"]
    trainable = {"tail": params["tail"], "prompt": params["prompt"]}
    n_tr = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(trainable))
    pb = trainable_nbytes(params)
    # edge 0 lost client 0, both edges live: uploads (1, 2) of sizes (2, 2)
    bd = hierarchical_secure_agg_breakdown(
        n_trainable=n_tr, param_nbytes=pb,
        edge_sizes=[2, 2], edge_uploads=[1.0, 2.0])
    for name in ("params", "secure", "edge_global"):
        got = sec.meter.totals[name]
        assert abs(got - bd[name]) <= 0.05 * bd[name], (name, got, bd[name])


def test_hierarchical_aggregator_validates_cohort_size():
    agg = HierarchicalAggregator(EdgeTopology(4, 2))
    tree = random_cohort_tree(KEY, 6)
    fb = jax.tree.map(lambda x: x[0], tree)
    with pytest.raises(ValueError, match="topology"):
        agg.aggregate(tree, jnp.ones((6,)), fb, 0)
    with pytest.raises(ValueError, match="no options"):
        HierarchicalAggregator(EdgeTopology(4, 2), impl="ref")


# --------------------------------------------------- MoE batched fallback
def test_moe_round_uses_batched_fallback():
    """MoE ragged ops have no vmap rule for unbatched operands — the
    trainer must detect that and fall back to K-broadcast frozen trees,
    and the round must still run end to end on token data."""
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced(n_layers=3)
    split = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=2,
                        prune_gamma=0.5, local_epochs=1)
    model = SplitModel(cfg, split)
    k = 2
    pcfg = ProtocolConfig(clients_per_round=k, local_epochs=1,
                          batch_size=2, momentum=0.0)
    tr = SFPromptTrainer(model, pcfg)
    assert tr._batch_frozen          # MoE -> broadcast path
    toks = synthetic_lm_dataset(k * N_LOCAL, 16, cfg.vocab_size,
                                seed=0)["tokens"]
    data = {"tokens": jnp.asarray(toks).reshape(k, N_LOCAL, -1)}
    state, metrics = tr.round(tr.init(KEY), data)
    assert np.isfinite(metrics["split_loss"])
    assert int(state["round"]) == 1


def test_dense_round_keeps_frozen_unbatched(setup):
    cfg, split = setup
    tr = make_trainer(cfg, split, k=2)
    assert not tr._batch_frozen      # dense -> in_axes=None frozen operands


# ------------------------------------------------- sharded cohort dispatch
@needs_mesh
@pytest.mark.parametrize("secure", [False, True],
                         ids=["clear", "secure"])
def test_sharded_round_matches_vmap_round(setup, secure):
    """K=64 as ONE sharded dispatch over the 8-device host mesh == the
    single-device vmap round: params and EVERY metric (including metered
    wire bytes) agree, under a straggler plan with dropouts."""
    cfg, split = setup
    k = 64
    data = cohort_batch(k)
    part = dropout_participation(k, n_dropped=5, n_late=3)

    def agg():
        return (get_aggregator(secure=True, impl="ref", seed=11)
                if secure else None)

    ref = make_trainer(cfg, split, k=k, aggregator=agg())
    st_r, m_r = ref.round(ref.init(KEY), data, dict(part))
    mesh = make_host_mesh()
    shard = make_trainer(cfg, split, k=k, aggregator=agg(), mesh=mesh)
    st_s, m_s = shard.round(shard.init(KEY), data, dict(part))

    for a, b in zip(jax.tree.leaves(st_r["params"]),
                    jax.tree.leaves(st_s["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert set(m_r) == set(m_s)
    for name in m_r:
        np.testing.assert_allclose(m_r[name], m_s[name], rtol=1e-5,
                                   err_msg=name)
    # the meter saw identical traffic on both layouts
    assert ref.meter.totals.keys() == shard.meter.totals.keys()
    for name in ref.meter.totals:
        np.testing.assert_allclose(ref.meter.totals[name],
                                   shard.meter.totals[name], rtol=1e-5,
                                   err_msg=name)


@needs_mesh
def test_sharded_round_body_stays_unbatched(setup):
    """The compiled sharded round must contain NO K-stacked copy of any
    frozen body leaf — phase-2 cohort HBM scales with K * (tail + prompt +
    opt state), not K * body. Checked against the compiled HLO text, with
    memory_analysis available as the accounting source."""
    cfg, split = setup
    k = 64
    data = cohort_batch(k)
    ones = jnp.ones((k,), jnp.float32)
    part = {"transmit": ones, "aggregate": ones}
    mesh = make_host_mesh()
    tr = make_trainer(cfg, split, k=k, mesh=mesh)
    state = tr.init(KEY)
    round_jit = tr._get_round_jit(state, data, part, None)
    compiled = round_jit.lower(state, data, part, None).compile()
    hlo = compiled.as_text()
    body_leaves = [x for x in jax.tree.leaves(state["params"]["body"])
                   if x.ndim >= 2]
    assert body_leaves
    for leaf in body_leaves:
        stacked = "f32[" + ",".join(str(d)
                                    for d in (k,) + leaf.shape) + "]"
        assert stacked not in hlo, (
            f"frozen body leaf {leaf.shape} appears K-stacked as {stacked}")
    assert compiled.memory_analysis() is not None


@needs_mesh
def test_sharded_jit_cache_reused_across_rounds(setup):
    """Repeated rounds at the same cohort shape reuse ONE mesh-jitted
    executable (no recompile per round)."""
    cfg, split = setup
    k = 8
    data = cohort_batch(k)
    tr = make_trainer(cfg, split, k=k, mesh=make_host_mesh())
    state = tr.init(KEY)
    state, _ = tr.round(state, data)
    assert len(tr._mesh_jit_cache) == 1
    state, _ = tr.round(state, data)
    assert len(tr._mesh_jit_cache) == 1
    assert int(state["round"]) == 2


@needs_mesh
def test_device_gathered_cohort_enters_donating_sharded_round(
        setup, monkeypatch):
    """A cohort gathered on the device enters the sharded round jit that
    donates it, as the launcher builds it over a mesh: two engine rounds
    equal those fed from the host gather, bit for bit, and the resident
    dataset survives the donations."""
    cfg, split = setup
    n_clients, k = 16, 8
    data = synthetic_image_dataset(DATASETS["cifar10-syn"],
                                   n_clients * N_LOCAL, seed=0, image_hw=32)
    mesh = make_host_mesh()

    def run():
        pop = Population.from_partition(data, n_clients, scheme="iid",
                                        seed=0)
        tr = SFPromptTrainer(
            SplitModel(cfg, split),
            ProtocolConfig(clients_per_round=k, local_epochs=1,
                           batch_size=BATCH, momentum=0.0),
            mesh=mesh, donate_cohort=True)
        eng = FederatedEngine(tr, pop, ClientSampler(n_clients, k, seed=5))
        eng.init(KEY)
        metrics = [eng.run_round()[1] for _ in range(2)]
        return eng, metrics

    dev, m_dev = run()
    monkeypatch.setattr(population_mod, "device_bytes_limit", lambda: 8)
    host, m_host = run()
    assert (dev.population.where, host.population.where) == \
        ("device", "host")
    assert m_dev == m_host
    for a, b in zip(jax.tree.leaves(dev.params),
                    jax.tree.leaves(host.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name, v in data.items():
        np.testing.assert_array_equal(np.asarray(dev.population.data[name]),
                                      v)


# ------------------------------------------------------------------ resume
def test_hierarchical_engine_resume_byte_identical(setup, tmp_path):
    """Kill-and-restart with a hierarchical aggregator: params, meter
    totals (including edge_global), and cohorts are byte-identical to the
    uninterrupted run — and a changed topology refuses the checkpoint."""
    cfg, split = setup
    n_clients, k = 40, 4
    data = synthetic_image_dataset(DATASETS["cifar10-syn"],
                                   n_clients * N_LOCAL, seed=0, image_hw=32)

    def build(n_edges=2):
        pop = Population.from_partition(data, n_clients, scheme="dirichlet",
                                        alpha=0.1, seed=0)
        tr = make_trainer(cfg, split, k=k,
                          aggregator=get_aggregator(n_edges=n_edges,
                                                    cohort_size=k))
        sampler = ClientSampler(pop.n_clients, k, kind="uniform", seed=7)
        sched = RoundScheduler(StragglerConfig(dropout_rate=0.25), seed=7)
        return FederatedEngine(tr, pop, sampler, sched)

    ref = build()
    ref.init(KEY)
    for _ in range(2):
        ref.run_round()

    eng = build()
    eng.init(KEY)
    eng.run_round()
    ckpt = str(tmp_path / "ckpt")
    eng.save(ckpt)

    # topology change must fail loudly — it is part of the fingerprint
    with pytest.raises(ValueError, match="trainer mismatch"):
        build(n_edges=4).restore(ckpt)

    res = build()
    assert res.restore(ckpt)
    res.run_round()
    for a, b in zip(jax.tree.leaves(ref.state["params"]),
                    jax.tree.leaves(res.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref.trainer.meter.as_dict() == res.trainer.meter.as_dict()
    assert ref.trainer.meter.totals["edge_global"] > 0
