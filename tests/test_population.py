"""Population-scale federated rounds: sampler determinism, straggler
scheduling, partial-participation FedAvg, partial-cohort wire metering vs
the analytical model, byte-identical kill-and-restart resume, and the
device-resident dataset's gather against the host gather."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ProtocolConfig, SFPromptTrainer, SplitConfig, SplitModel
from repro.core.aggregation import fedavg_partial
from repro.core.comm import (crosscheck, measured_cost_inputs,
                             sfprompt_comm_breakdown_partial)
import repro.fed.population as population_mod
from repro.data import DATASETS, synthetic_image_dataset
from repro.fed import (ClientSampler, FederatedEngine, Population,
                       RoundScheduler, StragglerConfig)
from repro.runtime import WireSpec

KEY = jax.random.PRNGKey(0)
N_CLIENTS = 1000
N_LOCAL = 8
BATCH = 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("vit-base").reduced(n_layers=3, d_model=32, d_ff=64)
    split = SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=2,
                        prune_gamma=0.3, local_epochs=1)
    data = synthetic_image_dataset(DATASETS["cifar10-syn"],
                                   N_CLIENTS * N_LOCAL, seed=0, image_hw=32)
    pop = Population.from_partition(data, N_CLIENTS, scheme="dirichlet",
                                    alpha=0.1, seed=0)
    return cfg, split, data, pop


def make_trainer(cfg, split, *, codec="fp32", k=4):
    model = SplitModel(cfg, split, WireSpec.make(codec))
    pcfg = ProtocolConfig(clients_per_round=k, local_epochs=1,
                          batch_size=BATCH, momentum=0.0)
    return SFPromptTrainer(model, pcfg)


# --------------------------------------------------------------- sampler
def test_sampler_determinism():
    for kind, w in (("uniform", None), ("round_robin", None),
                    ("weighted", np.arange(1.0, 101.0))):
        s = ClientSampler(100, 8, kind=kind, seed=5, weights=w)
        for r in (0, 3, 17):
            a, b = s.sample(r), s.sample(r)
            np.testing.assert_array_equal(a, b)
            assert len(set(a.tolist())) == 8      # without replacement
        assert not np.array_equal(s.sample(0), s.sample(1))


def test_sampler_round_robin_covers_population():
    s = ClientSampler(40, 8, kind="round_robin", seed=1)
    seen = set()
    for r in range(5):   # 5 * 8 == 40
        seen.update(s.sample(r).tolist())
    assert seen == set(range(40))


def test_weighted_sampler_skips_zero_weight_clients():
    w = np.ones(50)
    w[:25] = 0.0
    s = ClientSampler(50, 10, kind="weighted", seed=2, weights=w)
    for r in range(10):
        assert s.sample(r).min() >= 25


def test_sampler_state_roundtrip():
    a = ClientSampler(100, 8, kind="round_robin", seed=5)
    b = ClientSampler(100, 8, kind="round_robin", seed=999)
    b.load_state_dict(a.state_dict())
    for r in range(4):
        np.testing.assert_array_equal(a.sample(r), b.sample(r))
    with pytest.raises(ValueError):
        ClientSampler(100, 4, kind="uniform", seed=0).load_state_dict(
            a.state_dict())   # K mismatch must be loud


# ----------------------------------------------------------- aggregation
def test_fedavg_partial_weights():
    trees = {"w": jnp.stack([1.0 * jnp.ones(3), 3.0 * jnp.ones(3),
                             5.0 * jnp.ones(3)])}
    fallback = {"w": jnp.full((3,), -7.0)}
    # client 1 dropped: mean of {1 (w=2), 5 (w=2)} = 3
    out = fedavg_partial(trees, jnp.array([2.0, 0.0, 2.0]), fallback)
    np.testing.assert_allclose(np.asarray(out["w"]), 3.0 * np.ones(3))
    # unequal weights renormalize over survivors
    out = fedavg_partial(trees, jnp.array([1.0, 0.0, 3.0]), fallback)
    np.testing.assert_allclose(np.asarray(out["w"]), 4.0 * np.ones(3))
    # everyone dropped -> the round is lost, fallback returned
    out = fedavg_partial(trees, jnp.zeros(3), fallback)
    np.testing.assert_allclose(np.asarray(out["w"]), -7.0 * np.ones(3))


# -------------------------------------------------------------- scheduler
def test_scheduler_deterministic_and_bounded():
    sched = RoundScheduler(StragglerConfig(dropout_rate=0.4), seed=9)
    cohort = np.arange(16)
    a, b = sched.plan(cohort, 3), sched.plan(cohort, 3)
    np.testing.assert_array_equal(a.transmit, b.transmit)
    np.testing.assert_array_equal(a.aggregate, b.aggregate)
    assert (a.transmit >= 0).all() and (a.transmit <= 1).all()
    assert (a.aggregate >= 0).all()
    # dropped clients never aggregate; on-time clients fully transmit
    assert (a.aggregate[a.dropped] == 0).all()
    ontime = ~(a.dropped | a.late)
    assert (a.transmit[ontime] == 1).all()
    assert not np.array_equal(sched.plan(cohort, 4).dropped, a.dropped) or \
        not np.allclose(sched.plan(cohort, 4).latency_s, a.latency_s)


def test_scheduler_min_survivors():
    sched = RoundScheduler(StragglerConfig(dropout_rate=1.0,
                                           min_survivors=2), seed=0)
    plan = sched.plan(np.arange(8), 0)
    assert plan.n_active >= 2


def test_scheduler_partial_mode():
    cfg = StragglerConfig(deadline_factor=1.01, late_mode="partial",
                          partial_weight=0.25, speed_sigma=0.8)
    plan = RoundScheduler(cfg, seed=4).plan(np.arange(32), 0)
    assert plan.late.any()   # tight deadline + wide spread => stragglers
    # partial mode: late clients transmitted everything, aggregate reduced
    assert (plan.transmit[plan.late] == 1).all()
    assert (plan.aggregate[plan.late] == 0.25).all()


def test_scheduler_persistent_client_factors():
    sched = RoundScheduler(StragglerConfig(), seed=3)
    ids = np.array([5, 900, 31])
    link1, comp1 = sched.client_factors(ids)
    link2, comp2 = sched.client_factors(ids)
    np.testing.assert_allclose(link1, link2)
    np.testing.assert_allclose(comp1, comp2)
    assert not np.allclose(link1, comp1)   # independent draws


def test_scheduler_regime_changes_who_straggles():
    """LINK_REGIMES must be behavioral: on edge_wan the slow-LINK clients
    miss the deadline, in a datacenter the slow-COMPUTE ones do — the late
    sets and latencies differ across regimes for the same cohort."""
    cohort = np.arange(32)
    plans = {}
    for regime in ("edge_wan", "datacenter"):
        sched = RoundScheduler(
            StragglerConfig(regime=regime, deadline_factor=1.3), seed=3,
            round_bytes_per_client=2e6, round_flops_per_client=5e12)
        plans[regime] = sched.plan(cohort, 0)
    assert plans["edge_wan"].late.any()
    assert not np.array_equal(plans["edge_wan"].late,
                              plans["datacenter"].late)
    # absolute latencies scale with the link: edge_wan is slower overall
    assert (np.median(plans["edge_wan"].latency_s)
            > np.median(plans["datacenter"].latency_s))


def test_meter_per_client_round():
    from repro.runtime import TrafficMeter
    m = TrafficMeter()
    m.absorb({"head_body": 300.0, "params": 600.0}, clients=3)
    m.absorb({"head_body": 100.0, "params": 200.0}, clients=1)
    assert m.client_rounds == 4
    per = m.per_client_round()
    assert per["head_body"] == 100.0 and per["total"] == 300.0
    assert "active client-rounds" in m.report()


def test_sampler_streams_disjoint_from_scheduler():
    """Cohort draws and straggler draws must come from different RNG
    domains: SeedSequence drops trailing zeros, so an untagged sampler
    stream at round 7 would equal the scheduler's client-0 factor stream."""
    s = ClientSampler(1000, 8, kind="uniform", seed=0)
    for collision_word in (7, 11):   # scheduler domain tags
        untagged = np.random.default_rng(
            np.random.SeedSequence((0, collision_word)))
        assert not np.array_equal(
            s.sample(collision_word),
            np.asarray(untagged.choice(1000, size=8, replace=False),
                       dtype=np.int64))


# -------------------------------------------------------------- population
def test_population_gather_layout(setup):
    _, _, data, pop = setup
    assert pop.n_clients == N_CLIENTS
    cohort = [0, 500, 999]
    stacked = pop.gather(cohort)
    assert stacked["patches"].shape[:2] == (3, pop.n_local)
    # gathered rows really are that client's shard
    np.testing.assert_array_equal(
        stacked["labels"][1], data["labels"][pop.client_indices[500]])
    # alpha=0.1 Dirichlet: per-client label marginals are skewed
    fracs = []
    for cid in range(0, N_CLIENTS, 50):
        lbl = data["labels"][pop.client_indices[cid]]
        _, counts = np.unique(lbl, return_counts=True)
        fracs.append(counts.max() / counts.sum())
    assert np.mean(fracs) > 0.35


def test_population_participation_state(setup):
    _, _, _, pop_ref = setup
    pop = Population(pop_ref.data, pop_ref.client_indices, pop_ref.sizes)
    pop.record_participation([3, 7], 0)
    pop.record_participation([7], 1)
    assert pop.times_sampled[7] == 2 and pop.times_sampled[3] == 1
    assert pop.last_round[7] == 1 and pop.last_round[3] == 0
    state = pop.state_dict()
    pop2 = Population(pop_ref.data, pop_ref.client_indices, pop_ref.sizes)
    pop2.load_state_dict(state)
    np.testing.assert_array_equal(pop.times_sampled, pop2.times_sampled)
    # a DIFFERENT partition must refuse the state — resuming against
    # rebuilt-with-other-flags data silently diverges otherwise
    other = Population.from_partition(pop_ref.data, N_CLIENTS,
                                      scheme="iid", seed=1)
    with pytest.raises(ValueError, match="population mismatch"):
        other.load_state_dict(state)


def no_device_room(monkeypatch):
    """From here on the device reports too little memory for any dataset,
    so populations built after this keep their data on the host."""
    monkeypatch.setattr(population_mod, "device_bytes_limit", lambda: 8)


@pytest.mark.parametrize("scheme", ["iid", "dirichlet"])
def test_device_gather_equals_host_gather(setup, monkeypatch, scheme):
    """The resident dataset's gather returns what the host gather does,
    value for value and in dtype, for clients whose rows repeat too."""
    _, _, data, _ = setup

    def build():
        return Population.from_partition(data, N_CLIENTS, scheme=scheme,
                                          alpha=0.1, seed=0)
    dev = build()
    no_device_room(monkeypatch)
    host = build()
    assert (dev.where, host.where) == ("device", "host")
    short = np.flatnonzero(dev.sizes < dev.n_local)[:2].tolist()
    if scheme == "dirichlet":
        # padded clients resample their own rows
        assert short and len(np.unique(dev.client_indices[short[0]])) \
            < dev.n_local
    cohort = [0, 500, 999] + short
    got, want = dev.gather(cohort), host.gather(cohort)
    assert set(got) == set(want)
    for k in want:
        assert isinstance(got[k], jax.Array)
        assert isinstance(want[k], np.ndarray)
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])
    assert sum(v.nbytes for v in got.values()) == \
        sum(v.nbytes for v in want.values())
    assert dev.fingerprint() == host.fingerprint()


def test_dataset_over_the_share_stays_on_host(setup, monkeypatch):
    """The data goes to the device only within 1/DEVICE_SHARE of its
    memory; past that the host gather stays and returns numpy."""
    _, _, data, pop = setup
    nbytes = sum(v.nbytes for v in data.values())
    fits = nbytes * population_mod.DEVICE_SHARE
    for limit, where in [(fits, "device"), (fits - 1, "host")]:
        monkeypatch.setattr(population_mod, "device_bytes_limit",
                            lambda limit=limit: limit)
        p = Population(data, pop.client_indices, pop.sizes)
        assert p.where == where
    assert p.data is data
    out = p.gather([3, 7])
    assert all(type(v) is np.ndarray for v in out.values())
    np.testing.assert_array_equal(out["labels"][1],
                                  data["labels"][pop.client_indices[7]])


def test_fingerprint_reads_the_host_layout(monkeypatch):
    """int64 labels narrow to int32 on a device without x64; the
    partition's fingerprint still reads the data as given, so checkpoints
    of host-resident runs resume."""
    data = {"x": np.arange(24, dtype=np.float32).reshape(12, 2),
            "labels": np.arange(12, dtype=np.int64) % 3}
    dev = Population.from_partition(data, 3, seed=0)
    no_device_room(monkeypatch)
    host = Population.from_partition(data, 3, seed=0)
    assert dev.data["labels"].dtype == np.int32
    assert host.data["labels"].dtype == np.int64
    assert dev.fingerprint() == host.fingerprint()
    host.load_state_dict(dev.state_dict())


def test_gather_returns_fresh_buffers_safe_to_donate():
    """Each gather is a new buffer, never the resident dataset, even for
    the whole population in order; donating one leaves the data intact."""
    data = synthetic_image_dataset(DATASETS["cifar10-syn"], 4 * N_LOCAL,
                                   seed=0, image_hw=32)
    pop = Population.from_client_list(
        [{k: v[i * N_LOCAL:(i + 1) * N_LOCAL] for k, v in data.items()}
         for i in range(4)])
    whole = [0, 1, 2, 3]
    a, b = pop.gather(whole), pop.gather(whole)
    for k, v in data.items():
        ptrs = {x[k].unsafe_buffer_pointer() for x in (a, b, pop.data)}
        assert len(ptrs) == 3
        np.testing.assert_array_equal(
            np.asarray(a[k]).reshape(v.shape), v)
    consume = jax.jit(lambda d: jax.tree.map(lambda v: v + 1, d),
                      donate_argnums=0)
    jax.block_until_ready(consume(a))
    assert all(v.is_deleted() for v in a.values())
    for k, v in data.items():
        np.testing.assert_array_equal(np.asarray(pop.data[k]), v)
        np.testing.assert_array_equal(np.asarray(b[k]).reshape(v.shape), v)


def test_device_resident_round_matches_host_round(setup, monkeypatch):
    """One engine round over the device-resident population gives the
    same params, metrics and metered bytes, bit for bit, as the round fed
    from the host gather, and the same partition fingerprint."""
    cfg, split, data, _ = setup

    def run():
        pop = Population.from_partition(data, N_CLIENTS, scheme="dirichlet",
                                        alpha=0.1, seed=0)
        eng = FederatedEngine(
            make_trainer(cfg, split, k=4), pop,
            ClientSampler(pop.n_clients, 4, seed=3),
            RoundScheduler(StragglerConfig(dropout_rate=0.25), seed=3))
        eng.init(KEY)
        _, metrics = eng.run_round()
        return eng, metrics

    dev, m_dev = run()
    no_device_room(monkeypatch)
    host, m_host = run()
    assert (dev.population.where, host.population.where) == \
        ("device", "host")
    assert m_dev == m_host
    for a, b in zip(jax.tree.leaves(dev.params),
                    jax.tree.leaves(host.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert dev.trainer.meter.as_dict() == host.trainer.meter.as_dict()
    assert dev.population.fingerprint() == host.population.fingerprint()


# ------------------------------------------- cohort training + comm check
@pytest.mark.parametrize("k", [5, 32])
def test_population_cohort_comm_matches_analytical(setup, k):
    """A >=1000-client population trains via a sampled K-cohort with
    dropouts; the TrafficMeter's partial-cohort bytes match the analytical
    model within 5% (the comm_cost.py --check contract, now under
    stragglers)."""
    cfg, split, _, pop = setup
    tr = make_trainer(cfg, split, codec="int8", k=k)
    sampler = ClientSampler(pop.n_clients, k, kind="uniform", seed=11)
    sched = RoundScheduler(StragglerConfig(dropout_rate=0.3), seed=11)
    engine = FederatedEngine(tr, pop, sampler, sched)
    engine.init(KEY)
    plan, metrics = engine.run_round()
    # these seeds genuinely straggle (K=5: 1 dropped; K=32: 9 dropped,
    # 3 late) — the check below is a PARTIAL-cohort crosscheck, not the
    # synchronous one
    assert plan.n_active < k

    n_tokens = 1 + (32 // 16) ** 2
    ci = measured_cost_inputs(tr.model, tokens_per_sample=n_tokens,
                              n_local=N_LOCAL, batch_size=BATCH, K=k)
    analytical = sfprompt_comm_breakdown_partial(
        ci, transmit_sum=float(plan.transmit.sum()),
        n_uploads=plan.n_active, k_down=k)
    cc = crosscheck(tr.meter.totals, ci, analytical)
    assert set(cc) == {"head_body", "body_tail", "params"}
    for name, entry in cc.items():
        assert abs(entry["err_pct"]) <= 5.0, (name, entry)
    # dropped stragglers really removed traffic vs the synchronous round
    if plan.n_active < k:
        sync = sfprompt_comm_breakdown_partial(
            ci, transmit_sum=k, n_uploads=k, k_down=k)
        assert tr.meter.totals["params"] < sync["params"]


# ------------------------------------------------------------------ resume
def test_resume_is_byte_identical(setup, tmp_path):
    """Kill-and-restart: run rounds 0-1, checkpoint, restore in a FRESH
    engine/trainer, run round 2 — params, meter totals, and sampled cohorts
    must be byte-identical to the uninterrupted 3-round run."""
    cfg, split, data, _ = setup

    def build():
        pop = Population.from_partition(data, N_CLIENTS, scheme="dirichlet",
                                        alpha=0.1, seed=0)
        tr = make_trainer(cfg, split, k=4)
        sampler = ClientSampler(pop.n_clients, 4, kind="weighted", seed=7,
                                weights=pop.sizes.astype(float))
        sched = RoundScheduler(
            StragglerConfig(dropout_rate=0.25, late_mode="partial"), seed=7)
        return FederatedEngine(tr, pop, sampler, sched)

    # uninterrupted reference: 3 rounds
    ref = build()
    ref.init(KEY)
    for _ in range(3):
        ref.run_round()

    # interrupted run: 2 rounds, checkpoint, die
    eng = build()
    eng.init(KEY)
    for _ in range(2):
        eng.run_round()
    ckpt_dir = str(tmp_path / "ckpt")
    eng.save(ckpt_dir)

    # fresh process stand-in: new trainer, new population, restore, 1 round
    res = build()
    assert res.restore(ckpt_dir)
    assert res.round_idx == 2
    res.run_round()

    # cohort sequence identical: rounds 2 of both runs drew the same clients
    np.testing.assert_array_equal(ref.cohort_history[2],
                                  res.cohort_history[0])
    # params byte-identical
    for a, b in zip(jax.tree.leaves(ref.state["params"]),
                    jax.tree.leaves(res.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(ref.state["round"]) == int(res.state["round"]) == 3
    # meter totals identical (cumulative across the kill)
    assert ref.trainer.meter.as_dict() == res.trainer.meter.as_dict()
    assert ref.trainer.meter.rounds == res.trainer.meter.rounds
    # per-client participation state identical
    np.testing.assert_array_equal(ref.population.times_sampled,
                                  res.population.times_sampled)


def test_resume_with_changed_straggler_flags_fails_loudly(setup, tmp_path):
    """A checkpoint from one straggler config must not silently resume
    under another — the replayed plans would diverge from the
    uninterrupted run."""
    cfg, split, _, pop = setup
    tr = make_trainer(cfg, split, k=4)
    eng = FederatedEngine(
        tr, pop, ClientSampler(pop.n_clients, 4, seed=7),
        RoundScheduler(StragglerConfig(dropout_rate=0.25), seed=7))
    eng.state = tr.init(KEY)   # no training needed for the state check
    ckpt_dir = str(tmp_path / "ckpt")
    eng.save(ckpt_dir)
    other = FederatedEngine(
        make_trainer(cfg, split, k=4), pop,
        ClientSampler(pop.n_clients, 4, seed=7),
        RoundScheduler(StragglerConfig(dropout_rate=0.5), seed=7))
    with pytest.raises(ValueError, match="scheduler mismatch"):
        other.restore(ckpt_dir)
    # changed trainer hyperparameters must fail loudly too
    model_lr = SplitModel(cfg, split, WireSpec.make("fp32"))
    pcfg_lr = ProtocolConfig(clients_per_round=4, local_epochs=1,
                             batch_size=BATCH, momentum=0.0, lr_split=0.5)
    hot = FederatedEngine(
        SFPromptTrainer(model_lr, pcfg_lr), pop,
        ClientSampler(pop.n_clients, 4, seed=7),
        RoundScheduler(StragglerConfig(dropout_rate=0.25), seed=7))
    with pytest.raises(ValueError, match="trainer mismatch"):
        hot.restore(ckpt_dir)
    # a personalize_tails flip must also fail loudly, not silently diverge
    pcfg_pt = ProtocolConfig(clients_per_round=4, local_epochs=1,
                             batch_size=BATCH, momentum=0.0,
                             return_client_trainable=True)
    model_pt = SplitModel(cfg, split, WireSpec.make("fp32"))
    flipped = FederatedEngine(
        SFPromptTrainer(model_pt, pcfg_pt), pop,
        ClientSampler(pop.n_clients, 4, seed=7),
        RoundScheduler(StragglerConfig(dropout_rate=0.25), seed=7),
        personalize_tails=True)
    with pytest.raises(ValueError, match="personalize_tails mismatch"):
        flipped.restore(ckpt_dir)


def test_personalized_init_tails_enter_training(setup):
    """round(init_tails=...) really starts clients from the given tails:
    feeding the broadcast global tail reproduces the default round, a
    perturbed tail changes the aggregate."""
    cfg, split, _, pop = setup
    tr = make_trainer(cfg, split, k=2)
    state = tr.init(KEY)
    data = {k: jnp.asarray(v) for k, v in pop.gather([0, 1]).items()}
    ref_state, _ = tr.round(state, data)
    same = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (2,) + x.shape),
        state["params"]["tail"])
    same_state, _ = tr.round(state, data, None, same)
    for a, b in zip(jax.tree.leaves(ref_state["params"]["tail"]),
                    jax.tree.leaves(same_state["params"]["tail"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    bumped = jax.tree.map(lambda x: x + 0.1, same)
    diff_state, _ = tr.round(state, data, None, bumped)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ref_state["params"]["tail"]),
                        jax.tree.leaves(diff_state["params"]["tail"])))


def test_restore_empty_dir_is_noop(setup, tmp_path):
    cfg, split, _, pop = setup
    tr = make_trainer(cfg, split, k=4)
    engine = FederatedEngine(tr, pop,
                             ClientSampler(pop.n_clients, 4, seed=0))
    assert not engine.restore(str(tmp_path / "nothing"))
    assert engine.state is None
