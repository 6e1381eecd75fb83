"""Quickstart: the GraphBLAS graph database in 40 lines — the write path,
the paper's k-hop query, the algebraic plan, and the same query answered
over a device mesh (`mesh=`, PR 4's sharded surface).

  PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

from repro import compile_cache
from repro.engine import Database

compile_cache.enable()

db = Database(data_dir=tempfile.mkdtemp(prefix="repro_aof_"))

# write path (AOF-journaled, like Redis)
db.query("social", """CREATE (:Person {id: 0, age: 33}), (:Person {id: 1, age: 44}),
                     (:Person {id: 2, age: 25}), (:Person {id: 3, age: 61}),
                     (:City {id: 4})""")
db.query("social", "CREATE (0)-[:KNOWS]->(1), (1)-[:KNOWS]->(2), "
                   "(2)-[:KNOWS]->(3), (0)-[:KNOWS]->(2), (3)-[:VISITS]->(4)")

# the paper's benchmark query shape: k-hop neighborhood count
res = db.query("social", "MATCH (a)-[:KNOWS*1..2]->(b) WHERE id(a) = 0 "
                         "RETURN count(DISTINCT b)")
print("2-hop neighborhood of node 0:", res.scalar())

# property filters + projections
res = db.query("social", "MATCH (a:Person)-[:KNOWS]->(b:Person) "
                         "WHERE b.age > 30 RETURN a, b, b.age")
print("edges into >30-year-olds:", res.rows)

# the algebraic plan (Cypher -> linear algebra, the paper's contribution)
print("\nEXPLAIN:")
print(db.explain("social", "MATCH (a)-[:KNOWS*1..6]->(b) WHERE id(a) = 0 "
                           "RETURN count(DISTINCT b)"))

# sharded mode: the same query surface over a device mesh — pass mesh= and
# the context distributes every relation (grb.distribute); no other call
# site changes. On this host the mesh covers whatever devices exist.
import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

devs = np.array(jax.devices()).reshape(-1, 1, 1)
mesh = Mesh(devs, ("data", "pod", "model"))
res = db.query("social", "MATCH (a)-[:KNOWS*1..2]->(b) WHERE id(a) = 0 "
                         "RETURN count(DISTINCT b)", mesh=mesh)
print(f"\nsame answer on a {devs.size}-device mesh:", res.scalar())
