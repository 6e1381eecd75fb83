"""Database shell: CREATE/AOF replay, snapshots, batched query server."""
import numpy as np
import pytest

from repro.engine import Database, QueryServer, load_snapshot, save_snapshot
from repro.graph.datagen import social_graph
from repro.query.executor import execute
from repro.query.reference import execute_ref


def test_create_and_query(tmp_path):
    db = Database(data_dir=str(tmp_path))
    db.query("g", "CREATE (:Person {id: 0, age: 30}), (:Person {id: 1, age: 40}), "
                  "(:Person {id: 2, age: 50})")
    db.query("g", "CREATE (0)-[:KNOWS]->(1), (1)-[:KNOWS]->(2)")
    res = db.query("g", "MATCH (a)-[:KNOWS*1..2]->(b) WHERE id(a) = 0 "
                        "RETURN count(DISTINCT b)")
    assert res.scalar() == 2
    res = db.query("g", "MATCH (a:Person)-[:KNOWS]->(b) WHERE b.age > 45 "
                        "RETURN a, b")
    assert res.rows == [(1, 2)]


@pytest.mark.parametrize("write", ["CREATE (0)-[:KNOWS]->(5)",
                                   "DELETE (0)-[:KNOWS]->(1)"])
def test_bulk_loaded_graph_refuses_writes(tmp_path, write):
    """A load_graph graph is served as loaded: a write must raise (and
    reach no AOF) rather than be acknowledged and never read back."""
    db = Database(data_dir=str(tmp_path))
    db.load_graph("g", social_graph(n=64, seed=3))
    before = db.query("g", "MATCH (a)-[:KNOWS]->(b) RETURN count(b)").rows
    with pytest.raises(TypeError, match="bulk-loaded"):
        db.query("g", write)
    assert not any(p.is_file() and p.stat().st_size for p in tmp_path.iterdir())
    assert db.query("g", "MATCH (a)-[:KNOWS]->(b) RETURN count(b)").rows \
        == before


def test_aof_replay_recovers_after_crash(tmp_path):
    db = Database(data_dir=str(tmp_path))
    db.query("g", "CREATE (:Person {id: 0}), (:Person {id: 1}), (:Person {id: 2})")
    db.query("g", "CREATE (0)-[:KNOWS]->(1), (1)-[:KNOWS]->(2), (2)-[:KNOWS]->(0)")
    del db  # crash
    db2 = Database(data_dir=str(tmp_path))
    res = db2.query("g", "MATCH (a)-[:KNOWS*1..3]->(b) WHERE id(a) = 0 "
                         "RETURN count(DISTINCT b)")
    assert res.scalar() == 2  # reaches 1 and 2 (0 excluded as seed)


def test_delete_edge_and_node_forms(tmp_path):
    db = Database(data_dir=str(tmp_path))
    db.query("g", "CREATE (:Person {id: 0}), (:Person {id: 1}), "
                  "(:Person {id: 2})")
    db.query("g", "CREATE (0)-[:KNOWS]->(1), (1)-[:KNOWS]->(2), "
                  "(2)-[:KNOWS]->(0)")
    res = db.query("g", "DELETE (1)-[:KNOWS]->(2)")
    assert res.columns == ["nodes_deleted", "edges_deleted"]
    assert res.rows == [(0, 1)]
    assert db.query("g", "MATCH (a)-[:KNOWS*1..3]->(b) WHERE id(a) = 0 "
                         "RETURN count(DISTINCT b)").scalar() == 1
    # whole-node tombstone takes its incident edges with it
    res = db.query("g", "DELETE (0)")
    assert res.rows == [(1, 2)]       # (0)->(1) and (2)->(0)
    assert db.query("g", "MATCH (a)-[:KNOWS]->(b) "
                         "RETURN count(b)").scalar() == 0
    # deletes are AOF-logged: a crash-restart converges to the same state
    del db
    db2 = Database(data_dir=str(tmp_path))
    assert db2.query("g", "MATCH (a)-[:KNOWS]->(b) "
                          "RETURN count(b)").scalar() == 0


def test_create_auto_id_aof_round_trip(tmp_path):
    """create_node without an explicit {id: ...} auto-assigns next_id (the
    KeyError regression), and the assignment replays identically."""
    db = Database(data_dir=str(tmp_path))
    db.query("g", "CREATE (:Person {age: 30}), (:Person {age: 40})")
    db.query("g", "CREATE (0)-[:KNOWS]->(1)")
    rows = db.query("g", "MATCH (a:Person)-[:KNOWS]->(b) WHERE b.age > 35 "
                         "RETURN a, b").rows
    assert rows == [(0, 1)]
    del db
    db2 = Database(data_dir=str(tmp_path))
    assert db2._graph("g").next_id == 2
    assert db2.query("g", "MATCH (a:Person)-[:KNOWS]->(b) WHERE b.age > 35 "
                          "RETURN a, b").rows == rows


def test_snapshot_of_delta_served_graph(tmp_path):
    """save_snapshot on a mid-write-stream delta view captures the exact
    effective matrix (DeltaMatrix.to_coo composes it)."""
    db = Database()
    db.query("g", "CREATE (:Person {id: 0, age: 30}), "
                  "(:Person {id: 1, age: 40}), (:Person {id: 2, age: 50})")
    db.query("g", "CREATE (0)-[:KNOWS]->(1), (1)-[:KNOWS]->(2)")
    db.query("g", "MATCH (a)-[:KNOWS]->(b) RETURN count(b)")  # freeze a base
    db.query("g", "DELETE (0)-[:KNOWS]->(1)")
    db.query("g", "CREATE (2)-[:KNOWS]->(0)")                 # pending deltas
    g = db._graph("g").freeze()
    path = str(tmp_path / "snap.npz")
    save_snapshot(g, path)
    g2 = load_snapshot(path)
    q = "MATCH (a)-[:KNOWS*1..3]->(b) WHERE id(a) = 1 RETURN count(DISTINCT b)"
    assert execute(g2, q).rows == execute(g, q).rows
    assert g2.relation("KNOWS").A.nvals == 2


def test_snapshot_roundtrip(tmp_path):
    g = social_graph(n=128, seed=3)
    path = str(tmp_path / "snap.npz")
    save_snapshot(g, path)
    g2 = load_snapshot(path)
    q = "MATCH (a)-[:KNOWS*1..2]->(b) WHERE id(a) IN [1, 5, 9] RETURN a, count(DISTINCT b)"
    assert sorted(execute(g, q).rows) == sorted(execute(g2, q).rows)
    assert g2.nnz == g.nnz


def test_server_batches_compatible_queries():
    g = social_graph(n=256, seed=1)
    srv = QueryServer(g)
    qids, want = [], []
    for s in [1, 3, 5, 7, 11, 13]:
        q = (f"MATCH (a)-[:KNOWS*1..2]->(b) WHERE id(a) = {s} "
             f"RETURN count(DISTINCT b)")
        qids.append(srv.submit(q))
        want.append(execute_ref(g, q).rows)
    # one incompatible query rides along solo
    solo_q = "MATCH (a:City)<-[:VISITS]-(b) RETURN count(DISTINCT b)"
    solo_id = srv.submit(solo_q)
    out = srv.flush()
    for qid, w in zip(qids, want):
        assert out[qid].rows == w
    assert out[solo_id].rows == execute_ref(g, solo_q).rows
    assert srv.stats["batches"] == 1          # 6 queries -> 1 batch
    assert srv.stats["queries"] == 7
    assert srv.stats["solo"] == 1


def test_server_batch_matches_sequential():
    g = social_graph(n=256, seed=2)
    seeds = list(range(0, 60, 7))
    srv = QueryServer(g)
    qids = {s: srv.submit(f"MATCH (a)-[:KNOWS*1..3]->(b) WHERE id(a) = {s} "
                          f"RETURN count(DISTINCT b)") for s in seeds}
    out = srv.flush()
    for s in seeds:
        solo = execute(g, f"MATCH (a)-[:KNOWS*1..3]->(b) WHERE id(a) = {s} "
                          f"RETURN count(DISTINCT b)")
        assert out[qids[s]].rows == solo.rows
