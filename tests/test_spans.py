"""The span recorder: job and parent across threads, spans recorded after
the fact, the bounded buffer, and the self-time rule."""
import time

import numpy as np
import pytest

from repro.core import spans
from repro.core.dag import DAG, Node, State
from repro.core.executor import execute
from repro.core.omp import Materializer, Policy
from repro.core.store import Store
from repro.serve.pool import SharedWorkerPool


def _since(first_id: int) -> list:
    return [s for s in spans.recorded() if s.id > first_id]


def _mark() -> int:
    with spans.span("test.mark"):
        pass
    return spans.recorded()[-1].id


def _branch(i: int):
    def fn(x):
        time.sleep(0.01)   # long enough for every worker to take a node
        return x + i
    return fn


def _wide_dag(width: int) -> DAG:
    nodes = [Node("src", lambda: np.arange(64.0))]
    for i in range(width):
        nodes.append(Node(f"b{i}", _branch(i), parents=("src",)))
    nodes.append(Node("out", lambda *vs: float(np.sum(vs)),
                      parents=tuple(f"b{i}" for i in range(width)),
                      is_output=True))
    return DAG(nodes)


@pytest.mark.parametrize("pooled", [True, False], ids=["pool", "own"])
def test_job_and_parent_cross_worker_and_writer_threads(tmp_path, pooled):
    dag = _wide_dag(8)
    sigs = {n: f"sig-{n}" for n in dag.nodes}
    first = _mark()
    with spans.span("test.job", job="j7"):
        execute(dag, sigs, {n: State.COMPUTE for n in dag.nodes},
                Store(str(tmp_path / "store")),
                Materializer(policy=Policy.ALWAYS),
                async_materialization=True, max_workers=4,
                worker_pool=SharedWorkerPool(4) if pooled else None)
    got = _since(first)
    root = next(s for s in got if s.name == "test.job")
    run = next(s for s in got if s.name == "executor.run")
    assert run.parent == root.id
    assert {s.job for s in got if s.id != root.id} == {"j7"}
    nodes = [s for s in got if s.name == "executor.node"]
    assert len(nodes) == len(dag.nodes)
    assert {s.parent for s in nodes} == {run.id}
    worker = "helix-pool-worker" if pooled else "helix-exec-"
    assert any(s.thread.startswith(worker) for s in nodes)
    # Each queued save continues on the writer thread under its span.
    queued = {s.id: s for s in got
              if s.name == "store.save" and s.attrs.get("tier") == "queued"}
    written = [s for s in got
               if s.name == "store.save" and s.thread == "store-writer"]
    assert queued and len(written) == len(queued)
    assert {s.parent for s in written} == set(queued)
    assert all(queued[s.parent].parent == run.id for s in written)


def test_record_takes_a_past_interval_under_the_current_parent():
    with spans.span("test.outer", job="j1", k="v") as attrs:
        spans.record("test.past", 10, 25, bytes=3)
        attrs["late"] = 1
    past, outer = spans.recorded()[-2:]
    assert (past.name, past.start_ns, past.end_ns) == ("test.past", 10, 25)
    assert past.attrs == {"bytes": 3}
    assert (past.job, past.parent) == ("j1", outer.id)
    assert outer.attrs == {"k": "v", "late": 1} and outer.parent is None
    spans.record("test.other", 1, 2, job="j2")
    other = spans.recorded()[-1]
    assert (other.job, other.parent) == ("j2", None)


def test_buffer_keeps_the_newest_spans():
    for i in range(spans.MAX_SPANS + 5):
        spans.record("test.fill", i, i + 1)
    got = spans.recorded()
    assert len(got) == spans.MAX_SPANS
    assert got[0].start_ns == 5
    assert got[-1].start_ns == spans.MAX_SPANS + 4


def _span(id_, parent, start, end, name="s"):
    return spans.Span(name, "j", id_, parent, "t", start, end, {})


def test_self_time_subtracts_the_union_of_direct_children():
    root = _span(1, None, 0, 100)
    tree = [root,
            _span(2, 1, 10, 30),      # child
            _span(3, 1, 20, 40),      # overlaps the first: union 10-40
            _span(4, 3, 50, 90),      # grandchild outliving its parent
                                      # (a writer's save): not subtracted
            _span(5, 1, 90, 130),     # clipped to the root: 90-100
            _span(6, 9, 0, 100)]      # another tree
    assert spans.self_ns(root, tree) == 100 - 30 - 10
    assert spans.self_ns(tree[2], tree) == 20
    assert spans.self_ns(tree[3], tree) == 40
