"""Regression tests for the round-4 advisor findings (ADVICE.md r4).

Each test cites the finding it pins down:
- raft.py prev_term horizon sentinel -> Log Matching violation
- wal horizon-term persistence across reopen
- GET /clean_lock classified as a cluster WRITE
- router merge of version-skewed columnar/row partials
- Space.pre_expand_pids round-trip (scoped holder probes)
"""

import json

import numpy as np
import pytest

from vearch_tpu.cluster import auth as authmod
from vearch_tpu.cluster.entities import Space, TableSchema
from vearch_tpu.cluster.raft import RaftNode
from vearch_tpu.cluster.router import RouterServer
from vearch_tpu.cluster.wal import Wal


# -- WAL horizon term --------------------------------------------------------

def test_wal_horizon_term_survives_compaction_and_reopen(tmp_path):
    w = Wal(str(tmp_path))
    w.append([{"index": i, "term": 1 if i < 4 else 2, "op": {}}
              for i in range(1, 7)])
    assert w.term_at(0) == 0 and w.horizon_term == 0
    w.truncate_prefix(5)  # horizon = entry 4, term 2
    assert w.horizon_term == 2
    assert w.term_at(4) == 2  # answered from the persisted horizon
    assert w.term_at(3) is None  # genuinely gone
    w.close()
    w2 = Wal(str(tmp_path))
    assert w2.horizon_term == 2
    assert w2.term_at(4) == 2
    w2.reset(10, horizon_term=7)
    assert w2.term_at(9) == 7
    w2.close()
    w3 = Wal(str(tmp_path))
    assert (w3.first_index, w3.horizon_term) == (10, 7)


# -- raft: divergent uncommitted entry at the leader's snapshot horizon ------

def _mk_node(tmp_path, nid, members, registry, **kw):
    state = {"ops": []}

    def apply_fn(op):
        state["ops"].append(op)
        return True

    def snapshot_fn():
        with node._apply_lock:  # see test_raft_adversarial snapshot_fn
            return json.dumps(state["ops"]).encode(), node.applied

    def install_fn(data, _idx):
        state["ops"][:] = json.loads(data.decode())

    def send_fn(peer, path, body):
        target = registry[peer]
        if path.endswith("/append"):
            return target.handle_append(body)
        if path.endswith("/snapshot"):
            return target.handle_install_snapshot(body)
        raise AssertionError(f"unexpected route {path}")

    node = RaftNode(
        pid=1, node_id=nid, wal_dir=str(tmp_path / f"n{nid}"),
        apply_fn=apply_fn, send_fn=send_fn, members=members,
        is_leader=False, snapshot_fn=snapshot_fn, install_fn=install_fn,
        quorum_timeout=5.0, **kw,
    )
    node._test_state = state
    registry[nid] = node
    return node


def test_append_at_horizon_rejects_divergent_follower_entry(tmp_path):
    """Advisor r4 (raft.py:395): a follower holding a DIVERGENT
    uncommitted entry at exactly the leader's snapshot horizon must not
    keep it. The leader now sends the real horizon term (persisted in
    WAL meta); the follower detects the term mismatch, truncates, and
    converges via snapshot — it must never apply the divergent op.

    History: old leader A (term 1) appended entry 5 locally without
    quorum and died; B was promoted (term 2), wrote its own entry 5,
    committed + applied it, and compacted its log past index 5. A
    rejoins as a follower."""
    registry = {}
    a = _mk_node(tmp_path, 1, [1, 2], registry)
    b = _mk_node(tmp_path, 2, [1, 2], registry)

    shared = [{"index": i, "term": 1, "op": {"seq": i}} for i in range(1, 5)]
    # follower A: shared prefix applied, then the divergent orphan
    a.wal.append(shared)
    a.wal.commit_index = 4
    a._apply_to_commit()
    a.wal.append([{"index": 5, "term": 1, "op": {"who": "A-orphan"}}])
    a.wal.term = 1

    # leader B: shared prefix + ITS entry 5 (term 2), committed,
    # applied, then log compacted past the divergence point
    b.wal.append(shared)
    b.wal.term = 2
    b.wal.append([{"index": 5, "term": 2, "op": {"who": "B"}}])
    b.wal.commit_index = 5
    b._apply_to_commit()
    b.wal.truncate_prefix(6)  # horizon = 5, horizon_term = 2
    assert b.wal.horizon_term == 2

    b.become_leader(term=3, members=[1, 2])
    b._sync_peer(1, blocking=True)

    assert a._test_state["ops"] == b._test_state["ops"]
    assert {"who": "A-orphan"} not in a._test_state["ops"]
    assert a._test_state["ops"][-1] == {"who": "B"}
    assert a.applied == 5 and a.commit == 5
    # the catch-up crossed the horizon via a term-verified snapshot
    assert b.snapshots_sent == 1
    assert a.snapshots_installed == 1
    # and post-install appends at the horizon are term-verifiable
    b.propose([{"who": "B", "seq": 6}])
    assert a._test_state["ops"][-1] == {"who": "B", "seq": 6}
    a.close()
    b.close()


def test_unknown_horizon_committed_prev_index_matches(tmp_path):
    """Legacy meta (horizon term unknown): the leader's -1 sentinel is
    index-matched by a follower whose entry at prev is COMMITTED —
    safe, both committed histories are identical — so no snapshot storm
    (the pre-fix livelock: install loops forever because each install
    recreates the same unknowable horizon)."""
    registry = {}
    a = _mk_node(tmp_path, 1, [1, 2], registry)
    b = _mk_node(tmp_path, 2, [1, 2], registry)

    shared = [{"index": i, "term": 1, "op": {"seq": i}} for i in range(1, 4)]
    a.wal.append(shared)
    a.wal.commit_index = 3
    a._apply_to_commit()

    b.wal.append(shared)
    b.wal.commit_index = 3
    b._apply_to_commit()
    b.wal.truncate_prefix(4)
    b.wal.horizon_term = None  # simulate legacy meta without the field
    b.wal.save_meta()

    b.become_leader(term=2, members=[1, 2])
    b._sync_peer(1, blocking=True)
    assert b.snapshots_sent == 0  # sentinel append, no snapshot needed
    assert a._test_state["ops"] == b._test_state["ops"]
    b.propose([{"seq": 4}])
    assert a._test_state["ops"][-1] == {"seq": 4}
    a.close()
    b.close()


def test_unknown_horizon_uncommitted_divergence_snapshots(tmp_path):
    """Legacy meta + a follower holding an UNCOMMITTED divergent entry
    at the leader's unknowable horizon: the follower must NOT
    index-match (advisor r4) and must NOT truncate committed state — it
    nacks with its commit index, the leader walks back behind its
    horizon, and a real snapshot resolves it. The divergent op is never
    applied."""
    registry = {}
    a = _mk_node(tmp_path, 1, [1, 2], registry)
    b = _mk_node(tmp_path, 2, [1, 2], registry)

    shared = [{"index": i, "term": 1, "op": {"seq": i}} for i in range(1, 4)]
    # follower A: shared committed prefix + divergent uncommitted 4
    a.wal.append(shared)
    a.wal.commit_index = 3
    a._apply_to_commit()
    a.wal.append([{"index": 4, "term": 1, "op": {"who": "A-orphan"}}])

    # leader B: its own committed entry 4 (term 2), log compacted past
    # it, horizon term lost (legacy meta)
    b.wal.append(shared)
    b.wal.term = 2
    b.wal.append([{"index": 4, "term": 2, "op": {"who": "B"}}])
    b.wal.commit_index = 4
    b._apply_to_commit()
    b.wal.truncate_prefix(5)
    b.wal.horizon_term = None
    b.wal.save_meta()

    b.become_leader(term=3, members=[1, 2])
    b._sync_peer(1, blocking=True)
    assert b.snapshots_sent == 1
    assert a.snapshots_installed == 1
    assert a._test_state["ops"] == b._test_state["ops"]
    assert {"who": "A-orphan"} not in a._test_state["ops"]
    a.close()
    b.close()


# -- /clean_lock is a write --------------------------------------------------

def test_clean_lock_requires_write_privilege():
    """Advisor r4 (master.py:960): GET /clean_lock mutates state, so a
    blanket ReadOnly grant must not reach it."""
    resource, needed = authmod.parse_resources("/clean_lock", "GET")
    assert resource == authmod.RESOURCE_CLUSTER
    assert needed == authmod.PRIVI_WRITE
    with pytest.raises(Exception, match="admin surface"):
        authmod.has_permission(
            "reader", {authmod.RESOURCE_ALL: authmod.PRIVI_READ},
            "/clean_lock", "GET")
    # plain cluster reads keep working for readers
    authmod.has_permission(
        "reader", {authmod.RESOURCE_ALL: authmod.PRIVI_READ},
        "/cluster/stats", "GET")


# -- mixed columnar/row merge ------------------------------------------------

def test_merge_search_mixed_columnar_and_row_partials():
    """Advisor r4 (router.py:715): one PS answering columnar and another
    rows (version skew) must merge, not KeyError."""
    router = object.__new__(RouterServer)  # _merge_search touches no state
    columnar = {
        "metric": "L2", "columnar": True,
        "keys": [["a", "b"], ["c"]],
        "scores": np.asarray([0.1, 0.3, 0.2], dtype=np.float32),
    }
    rows = {
        "metric": "L2",
        "results": [
            [{"_id": "x", "_score": 0.2}],
            [{"_id": "y", "_score": 0.05}],
        ],
    }
    merged = RouterServer._merge_search(router, [columnar, rows], k=2)
    assert [r["_id"] for r in merged[0]] == ["a", "x"]  # 0.1 < 0.2 < 0.3
    assert [r["_id"] for r in merged[1]] == ["y", "c"]  # 0.05 < 0.2
    # all-columnar fast path still intact
    merged2 = RouterServer._merge_search(router, [columnar], k=1)
    assert [r["_id"] for r in merged2[0]] == ["a"]
    # all-row slow path still intact
    merged3 = RouterServer._merge_search(router, [rows], k=1)
    assert [r["_id"] for r in merged3[0]] == ["x"]


def _merge_columnar_by_query(partials, k):
    """The per-query merge `_merge_search` ran until PR 26 (a
    concatenate, a stable argsort and a take for every query row), kept
    here as the reference of its order."""
    reverse = partials[0]["metric"] != "L2"
    out = []
    offs = [np.cumsum([0] + [len(ks) for ks in p["keys"]]) for p in partials]
    for qi in range(len(partials[0]["keys"])):
        keys = [key for p in partials for key in p["keys"][qi]]
        scores = np.concatenate([
            np.asarray(p["scores"])[o[qi]:o[qi + 1]]
            for p, o in zip(partials, offs)])
        order = np.argsort(-scores if reverse else scores,
                           kind="stable")[:k]
        out.append([{"_id": keys[i], "_score": s}
                    for i, s in zip(order.tolist(), scores[order].tolist())])
    return out


@pytest.mark.parametrize("metric", ["L2", "InnerProduct"])
@pytest.mark.parametrize("n_parts", [1, 3])
@pytest.mark.parametrize("ragged", [False, True])
def test_merge_search_columnar_is_one_sort_with_the_old_order(
        metric, n_parts, ragged):
    """The columnar merge sorts the whole reply at once. Same hits, same
    scores, same order as the per-query merge, ties included (scores
    drawn from 7 values: partition order, then the partition's own),
    with queries that got fewer hits than k, or none."""
    rng = np.random.default_rng(n_parts + 10 * ragged)
    nq, k = 64, 10
    partials = []
    for part in range(n_parts):
        lens = (rng.integers(0, k + 1, nq) if ragged
                else np.full(nq, k)).tolist()
        partials.append({
            "metric": metric, "columnar": True,
            "keys": [[f"p{part}q{qi}h{j}" for j in range(n)]
                     for qi, n in enumerate(lens)],
            "scores": rng.integers(0, 7, sum(lens)).astype(np.float32),
        })
    router = object.__new__(RouterServer)
    merged = RouterServer._merge_search(router, partials, k)
    assert merged == _merge_columnar_by_query(partials, k)
    assert all(type(hit["_score"]) is float for row in merged for hit in row)
    assert RouterServer._merge_search(router, partials, 3) == \
        _merge_columnar_by_query(partials, 3)


# -- pre_expand_pids round-trip ----------------------------------------------

def test_space_pre_expand_pids_roundtrip():
    schema = TableSchema(name="t", fields=[])
    sp = Space(id=1, name="s", db_name="d",
               schema=schema, expanded=True,
               pre_expand_pids=[3, 1, 2])
    d = sp.to_dict()
    assert d["pre_expand_pids"] == [3, 1, 2]
    back = Space.from_dict(d)
    assert back.pre_expand_pids == [3, 1, 2]
    # absent for never-expanded spaces (wire compat)
    sp2 = Space(id=2, name="s2", db_name="d", schema=schema)
    assert "pre_expand_pids" not in sp2.to_dict()
