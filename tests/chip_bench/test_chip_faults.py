"""``correct`` comes out false when the timed path is broken underneath:
a step that returns its state unchanged, half of each batch left out,
an answer altered where it is produced, and a reused state that the
store hands back stale or altered."""
import jax
import jax.numpy as jnp
import pytest

from conftest import TEST_LIMITS
from repro.core.store import Store


def _unchanged(pr, monkeypatch):
    step = pr.train

    def broken(state, batch):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        _, metrics = step(state, batch)
        return keep, metrics
    pr.train = broken


def _half_batch(pr, monkeypatch):
    step = pr.train

    def broken(state, batch):
        tok = batch["tokens"]
        return step(state, {"tokens": tok[: tok.shape[0] // 2]})
    pr.train = broken


def _altered_answer(pr, monkeypatch):
    nll = pr.eval_nll
    pr.eval_nll = lambda params, toks: nll(params, toks).at[0, 3].add(1.0)


def _reload(swap):
    """A fault in the store's load: the trained state it hands back is
    ``swap(initial state, loaded state)``; the training logs that ride
    along are the true ones."""
    def plant(pr, monkeypatch):
        init, first = pr.init, []

        def recording(key):
            state = init(key)
            first.append(state)
            return state
        pr.init = recording
        load = Store.load

        def broken(self, sig, sharding_for_leaf=None):
            value, seconds = load(self, sig, sharding_for_leaf)
            if isinstance(value, dict) and "state" in value:
                value = {**value, "state": swap(first[0], value["state"])}
            return value, seconds
        monkeypatch.setattr(Store, "load", broken)
    return plant


def _one_element_changed(_, state):
    leaf = state.params["final_norm"]
    return state._replace(params={**state.params,
                                  "final_norm": leaf.at[5].add(1e-3)})


@pytest.mark.parametrize("workload,plant", [
    ("internlm2-1.8b-2l.dpr", _unchanged),
    ("internlm2-1.8b-2l.dpr", _half_batch),
    ("internlm2-1.8b-2l.dpr", _altered_answer),
    ("internlm2-1.8b-2l.ppr", _reload(lambda first, _: first)),
    ("internlm2-1.8b-2l.ppr", _reload(_one_element_changed)),
], ids=["unchanged", "half_batch", "altered_answer", "stale_reload",
        "altered_reload"])
def test_fault_makes_run_incorrect(harness, monkeypatch, workload, plant,
                                   tmp_path):
    r = harness.run(workload, 11, 0.5, False, test_sizes=True,
                    require_chip=False,
                    plant=lambda pr: plant(pr, monkeypatch),
                    limits=TEST_LIMITS, work=str(tmp_path))
    assert not r["correct"], r["checks"]
    if plant not in (_unchanged, _half_batch, _altered_answer):
        assert r["checks"]["reload_mismatches"]["value"] > 0, r["checks"]
