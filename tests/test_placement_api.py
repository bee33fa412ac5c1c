"""Placement API surface (src/repro/relay/placement.py) + FleetConfig as
the trainers' one fleet argument.

Pins the contracts the placement redesign introduced: every relay-side
state kind declares its placement (`out_spec`), `resolve` turns those
declarations into NamedShardings, `exchange` is a no-op off-mesh, the
sequential oracle rejects a mesh with an error that says why, and both
trainers take the fleet as `fleet=FleetConfig(...)` only — the loose
pre-FleetConfig kwargs and the `repro.core.server` re-export are retired
shims, so passing the one is a TypeError and importing the other a
ModuleNotFoundError.
"""
import importlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import relay as relay_lib, sharding
from repro.core import client as client_lib, collab, vec_collab
from repro.data import partition, synthetic
from repro.models import mlp
from repro.relay import events, history, placement
from repro.types import CollabConfig, FleetConfig, TrainConfig

SPEC = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))


def _fleet_args(n_clients=2, n=64, seed=0):
    x, y = synthetic.class_images(n, seed=0, noise=0.4)
    parts = partition.uniform_split(x, y, n_clients, seed=1)
    ccfg = CollabConfig(num_classes=10, d_feature=84)
    params = [mlp.init_mlp(k)
              for k in jax.random.split(jax.random.PRNGKey(seed), n_clients)]
    return ([SPEC] * n_clients, params, parts,
            synthetic.class_images(32, seed=9), ccfg, TrainConfig())


# ---------------------------------------------------------------------------
# placement primitives
# ---------------------------------------------------------------------------
def test_like_tags_every_leaf():
    tree = {"a": jnp.zeros((2,)), "b": (jnp.zeros(()), jnp.ones((3, 4)))}
    tags = placement.like(tree, placement.REPLICATED)
    assert jax.tree.structure(tags) == jax.tree.structure(tree)
    assert set(jax.tree.leaves(tags)) == {placement.REPLICATED}
    with pytest.raises(ValueError, match="unknown placement"):
        placement.like(tree, "diagonal")


def test_resolve_maps_tags_to_shardings():
    mesh = sharding.client_mesh(1)
    rep = placement.resolve(placement.REPLICATED, mesh)
    cl = placement.resolve(placement.CLIENT_SHARDED, mesh)
    assert rep.spec == jax.sharding.PartitionSpec()
    assert cl.spec == jax.sharding.PartitionSpec(placement.CLIENT_AXIS)
    tree = {"a": placement.REPLICATED, "b": placement.CLIENT_SHARDED}
    rs = placement.resolve(tree, mesh)
    assert rs["a"].spec == rep.spec and rs["b"].spec == cl.spec


def test_exchange_is_noop_off_mesh():
    x = {"p": jnp.arange(4.0), "q": jnp.ones((2, 3))}
    out = placement.exchange(x, None)
    assert out is x                                   # structurally free
    mesh = sharding.client_mesh(1)
    out = placement.exchange(x, mesh)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), x, out)


@pytest.mark.parametrize("policy", ["flat", "per_class", "staleness"])
def test_every_policy_declares_replicated_state(policy):
    """The relay IS the paper's shared pool: every policy's state leaves
    are REPLICATED, leaf for leaf."""
    pol = relay_lib.get_policy(policy)
    st = pol.init_state(CollabConfig(num_classes=4, d_feature=3), 3, seed=0)
    spec = pol.out_spec(st)
    assert jax.tree.structure(spec) == jax.tree.structure(st)
    assert set(jax.tree.leaves(spec)) == {placement.REPLICATED}


def test_pending_is_client_sharded_history_replicated():
    pending = events.init_pending(4, 2, 1, 4, 3)
    pspec = events.out_spec(pending)
    assert set(jax.tree.leaves(pspec)) == {placement.CLIENT_SHARDED}
    pol = relay_lib.get_policy("flat")
    st = pol.init_state(CollabConfig(num_classes=4, d_feature=3), 3, seed=0)
    hist = history.init(st, 2)
    assert set(jax.tree.leaves(history.out_spec(hist))) == {
        placement.REPLICATED}


# ---------------------------------------------------------------------------
# engine API: seq rejects mesh with a WHY, vec compiles once (1-device)
# ---------------------------------------------------------------------------
def test_sequential_oracle_rejects_mesh():
    with pytest.raises(ValueError, match="sequential oracle.*host-side"):
        collab.CollabTrainer(*_fleet_args(),
                             fleet=FleetConfig(mesh=sharding.client_mesh(1)))


def test_placement_round_step_compiles_once():
    vec = vec_collab.VectorizedCollabTrainer(
        *_fleet_args(n=96), seed=0,
        fleet=FleetConfig(mesh=sharding.client_mesh(1)))
    for _ in range(3):
        vec.run_round()
    assert vec._round_step._cache_size() == 1


# ---------------------------------------------------------------------------
# retired shims
# ---------------------------------------------------------------------------
_VEC = vec_collab.VectorizedCollabTrainer
_SEQ = collab.CollabTrainer


@pytest.mark.parametrize("engine, kwarg, value", [
    (_VEC, "mesh", None), (_VEC, "policy", "flat"),
    (_VEC, "schedule", "uniform_k:1"), (_VEC, "clock", "lognormal:2"),
    (_VEC, "download_clock", "lognormal:2"),
    (_SEQ, "policy", "flat"), (_SEQ, "schedule", "uniform_k:1"),
    (_SEQ, "clock", "lognormal:2"), (_SEQ, "download_clock", "lognormal:2"),
], ids=lambda v: getattr(v, "__name__", v))
def test_trainers_take_fleet_config_only(engine, kwarg, value):
    """The pre-FleetConfig kwargs are gone from both engines: a fleet is
    `fleet=FleetConfig(...)` and nothing else."""
    with pytest.raises(TypeError, match=kwarg):
        engine(*_fleet_args(), seed=0, **{kwarg: value})


def test_core_server_shim_is_retired():
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.core.server")


def test_no_internal_module_triggers_shims():
    """Importing the whole package tree must raise no repro: deprecation
    (the filterwarnings=error line in pyproject only covers test runs;
    this pins it for plain imports too)."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        for m in ("repro.core.vec_collab", "repro.core.collab",
                  "repro.relay", "repro.launch.train"):
            importlib.import_module(m)
