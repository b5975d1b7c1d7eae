"""Graph library — the Gelly analog, TPU-native.

The reference's Gelly (``flink-libraries/flink-gelly``, ~60k LoC of
DataSet-based graph algorithms + iteration abstractions) re-designed as
dense array programs: a graph is (num_vertices, edge src[int32], edge
dst[int32], optional edge weights), algorithms are ``jax.ops.segment_sum``
message passing inside jitted supersteps — the scatter-gather /
vertex-centric model (``spargel``) IS one segment-sum per superstep on TPU.

Algorithms (the ``flink-gelly`` ``library/`` roster): PageRank, connected
components, SSSP (Bellman-Ford relaxation), triangle count, k-core, local
clustering coefficient, BFS levels, label propagation, HITS, per-edge
Jaccard similarity and Adamic-Adar, structural summarization (contract by
label), bipartite projections, aggregate vertex metrics — plus the
generic ``scatter_gather`` harness the rest are built on.  ``scatter_gather``/``pagerank`` take a ``mesh`` to run
EDGE-SHARDED over a device mesh (shard_map segment-combine per device, one
``psum``/``pmin``/``pmax`` over ICI per superstep).  Interop with the
DataSet API both ways (``from_dataset`` / ``as_dataset``).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


#: combine kind -> segment op (single source of truth for both the
#: single-device and mesh supersteps)
_SEGMENT_OPS = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
                "max": jax.ops.segment_max}


class Graph:
    def __init__(self, num_vertices: int, src: np.ndarray, dst: np.ndarray,
                 weights: Optional[np.ndarray] = None):
        self.n = int(num_vertices)
        self.src = jnp.asarray(src, jnp.int32)
        self.dst = jnp.asarray(dst, jnp.int32)
        self.weights = (jnp.asarray(weights, jnp.float32)
                        if weights is not None else None)

    # -- construction --------------------------------------------------------
    @staticmethod
    def from_edges(edges, num_vertices: Optional[int] = None,
                   weights=None) -> "Graph":
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        n = num_vertices if num_vertices is not None else (int(e.max()) + 1
                                                           if e.size else 0)
        return Graph(n, e[:, 0], e[:, 1], weights)

    @staticmethod
    def from_dataset(ds, src_column: str = "src", dst_column: str = "dst",
                     weight_column: Optional[str] = None,
                     num_vertices: Optional[int] = None) -> "Graph":
        b = ds.collect_batch()
        src = np.asarray(b.column(src_column))
        dst = np.asarray(b.column(dst_column))
        n = num_vertices if num_vertices is not None else (
            int(max(src.max(), dst.max())) + 1 if len(b) else 0)
        w = np.asarray(b.column(weight_column)) if weight_column else None
        return Graph(n, src, dst, w)

    def as_dataset(self):
        from flink_tpu.dataset import ExecutionEnvironment
        env = ExecutionEnvironment()
        cols = {"src": np.asarray(self.src), "dst": np.asarray(self.dst)}
        if self.weights is not None:
            cols["weight"] = np.asarray(self.weights)
        return env.from_columns(cols)

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])

    def undirected(self) -> "Graph":
        """Add reverse edges (``Graph.getUndirected``)."""
        return Graph(self.n,
                     jnp.concatenate([self.src, self.dst]),
                     jnp.concatenate([self.dst, self.src]),
                     None if self.weights is None
                     else jnp.concatenate([self.weights, self.weights]))

    # -- degrees -------------------------------------------------------------
    def out_degrees(self) -> np.ndarray:
        return np.asarray(jax.ops.segment_sum(
            jnp.ones_like(self.src, jnp.int32), self.src, self.n))

    def in_degrees(self) -> np.ndarray:
        return np.asarray(jax.ops.segment_sum(
            jnp.ones_like(self.dst, jnp.int32), self.dst, self.n))

    # -- generic scatter-gather (vertex-centric supersteps) ------------------
    def scatter_gather(self, initial_values: np.ndarray,
                       message_fn: Callable,
                       combine: str,
                       update_fn: Callable,
                       max_supersteps: int,
                       converged: Optional[Callable] = None,
                       mesh=None) -> np.ndarray:
        """Vertex-centric iteration (``ScatterGatherIteration`` analog).

        Per superstep (one jitted step): ``msgs = message_fn(values[src],
        weights)`` scattered to dst with ``combine`` (sum/min/max), then
        ``values' = update_fn(values, combined)``. Stops at
        ``max_supersteps`` or when ``converged(old, new)`` is True.

        ``mesh``: a ``jax.sharding.Mesh`` — EDGES shard across devices
        (the natural SPMD cut for message passing), vertex values
        replicate; each device segment-combines its local messages and the
        partials merge with one collective per superstep (``psum`` /
        ``pmin`` / ``pmax`` over ICI).  Combine identities pad the edge
        list to a device-divisible length."""
        if mesh is None:
            seg = _SEGMENT_OPS[combine]

            @jax.jit
            def superstep(values):
                msgs = message_fn(values[self.src], self.weights)
                combined = seg(msgs, self.dst, self.n)
                return update_fn(values, combined)
        else:
            superstep = self._mesh_superstep(mesh, message_fn, combine,
                                             update_fn)

        values = jnp.asarray(initial_values)
        for _ in range(max_supersteps):
            new = superstep(values)
            if converged is not None and bool(converged(values, new)):
                values = new
                break
            values = new
        return np.asarray(values)

    def _mesh_superstep(self, mesh, message_fn: Callable, combine: str,
                        update_fn: Callable):
        """Edge-sharded superstep: pad edges to D-divisible, shard_map the
        local segment-combine, merge partials with the matching collective."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        D = mesh.devices.size
        axis = mesh.axis_names[0]
        E = self.src.shape[0]
        Ep = -(-max(E, 1) // D) * D
        # padding rows scatter the combine's identity to vertex 0
        pad_src = jnp.zeros(Ep - E, jnp.int32)
        pad_dst = jnp.zeros(Ep - E, jnp.int32)
        src_p = jnp.concatenate([self.src, pad_src])
        dst_p = jnp.concatenate([self.dst, pad_dst])
        w = self.weights
        if w is not None:
            w = jnp.concatenate([w, jnp.zeros(Ep - E, w.dtype)])
        valid = jnp.concatenate([jnp.ones(E, bool), jnp.zeros(Ep - E, bool)])
        seg = _SEGMENT_OPS[combine]
        coll = {"sum": jax.lax.psum, "min": jax.lax.pmin,
                "max": jax.lax.pmax}[combine]

        def ident_of(dtype):
            if combine == "sum":
                return jnp.zeros((), dtype)
            if jnp.issubdtype(dtype, jnp.integer):
                info = jnp.iinfo(dtype)
                return jnp.asarray(info.max if combine == "min"
                                   else info.min, dtype)
            return jnp.asarray(jnp.inf if combine == "min" else -jnp.inf,
                               dtype)

        n = self.n
        espec = P(axis)
        shard = NamedSharding(mesh, espec)
        src_p = jax.device_put(src_p, shard)
        dst_p = jax.device_put(dst_p, shard)
        valid = jax.device_put(valid, shard)
        if w is not None:
            w = jax.device_put(w, shard)

        in_specs = (P(), espec, espec, espec) + ((espec,) if w is not None
                                                 else ())

        @partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                 out_specs=P(), check_vma=False)
        def local_combine(values, src_l, dst_l, valid_l, *w_l):
            msgs = message_fn(values[src_l], w_l[0] if w_l else None)
            # broadcast the edge mask over any trailing value dims (vector
            # vertex values must behave exactly like the single-device path)
            mask = valid_l.reshape(valid_l.shape + (1,) * (msgs.ndim - 1))
            msgs = jnp.where(mask, msgs, ident_of(msgs.dtype))
            part = seg(msgs, dst_l, n)
            return coll(part, axis)

        @jax.jit
        def superstep(values):
            args = (values, src_p, dst_p, valid) + ((w,) if w is not None
                                                    else ())
            combined = local_combine(*args)
            return update_fn(values, combined)

        return superstep

    # -- algorithms ----------------------------------------------------------
    def pagerank(self, damping: float = 0.85, num_iterations: int = 30,
                 tol: float = 0.0, mesh=None) -> np.ndarray:
        """Power iteration with dangling-mass redistribution (``PageRank``).

        ``mesh``: run edge-sharded over a device mesh — per-edge
        contributions carry 1/out_degree as edge weights, each device
        segment-sums its shard, partials ``psum`` over ICI, and the
        dangling-mass/teleport update runs on the replicated rank vector."""
        n = self.n
        out_deg = jnp.asarray(self.out_degrees(), jnp.float32)
        dangling = out_deg == 0
        safe_deg = jnp.where(dangling, 1.0, out_deg)
        if mesh is not None:
            inv_deg_e = (1.0 / np.asarray(safe_deg))[np.asarray(self.src)]
            g = Graph(n, self.src, self.dst, inv_deg_e)

            def msg(vals, w):
                return vals * w

            def update(ranks, spread):
                dm = jnp.sum(jnp.where(dangling, ranks, 0.0))
                return (1.0 - damping) / n + damping * (spread + dm / n)

            conv = ((lambda a, b: bool(jnp.abs(b - a).sum() < tol))
                    if tol else None)
            return g.scatter_gather(
                jnp.full(n, 1.0 / n, jnp.float32), msg, "sum", update,
                num_iterations, conv, mesh=mesh)

        @jax.jit
        def step(ranks):
            contrib = ranks / safe_deg
            spread = jax.ops.segment_sum(contrib[self.src], self.dst, n)
            dangling_mass = jnp.sum(jnp.where(dangling, ranks, 0.0))
            return ((1.0 - damping) / n
                    + damping * (spread + dangling_mass / n))

        ranks = jnp.full(n, 1.0 / n, jnp.float32)
        for _ in range(num_iterations):
            new = step(ranks)
            if tol and float(jnp.abs(new - ranks).sum()) < tol:
                ranks = new
                break
            ranks = new
        return np.asarray(ranks)

    def connected_components(self, max_supersteps: int = 0) -> np.ndarray:
        """Min-label propagation over the undirected graph
        (``ConnectedComponents`` delta-iteration analog)."""
        g = self.undirected()
        steps = max_supersteps or self.n

        def msg(vals, _w):
            return vals

        def update(vals, combined):
            return jnp.minimum(vals, combined)

        return g.scatter_gather(
            jnp.arange(self.n, dtype=jnp.int32), msg, "min", update, steps,
            converged=lambda a, b: bool(jnp.array_equal(a, b)))

    def sssp(self, source: int, num_iterations: int = 0) -> np.ndarray:
        """Single-source shortest paths (``SingleSourceShortestPaths``):
        Bellman-Ford relaxation, one segment_min per superstep."""
        inf = jnp.float32(jnp.inf)
        w = (self.weights if self.weights is not None
             else jnp.ones_like(self.src, jnp.float32))
        dist0 = jnp.full(self.n, inf, jnp.float32).at[source].set(0.0)
        steps = num_iterations or self.n

        def msg(vals, weights):
            return vals + weights

        def update(vals, combined):
            return jnp.minimum(vals, combined)

        def message_fn(src_vals, weights):
            return msg(src_vals, w)

        return self.scatter_gather(
            dist0, message_fn, "min", update, steps,
            converged=lambda a, b: bool(jnp.array_equal(a, b)))

    def triangle_count(self) -> int:
        """Total triangles (``TriangleEnumerator`` analog): dense adjacency
        trace(A^3)/6 for small graphs, neighbor-set intersection otherwise."""
        n = self.n
        if n <= 2048:
            # float64 on host: a float32 MXU trace loses exactness past
            # 2^24 triangles; counts must be exact
            a = np.zeros((n, n), np.float64)
            src_np, dst_np = np.asarray(self.src), np.asarray(self.dst)
            a[src_np, dst_np] = 1.0
            a[dst_np, src_np] = 1.0
            np.fill_diagonal(a, 0.0)  # drop self loops
            t = np.trace(a @ a @ a)
            return int(round(t / 6.0))
        # host fallback: sorted adjacency intersection
        src = np.asarray(self.src)
        dst = np.asarray(self.dst)
        adj = {}
        for s, d in zip(src.tolist(), dst.tolist()):
            if s == d:
                continue
            adj.setdefault(s, set()).add(d)
            adj.setdefault(d, set()).add(s)
        count = 0
        for v, nbrs in adj.items():
            for u in nbrs:
                if u > v:
                    count += len(nbrs & adj.get(u, set())
                                 & {x for x in adj.get(u, set()) if x > u})
        return count

    def k_core(self, k: int, max_iterations: int = 0) -> np.ndarray:
        """bool[n] membership in the k-core (``KCore`` analog): iteratively
        peel vertices with degree < k — vectorized per round.  Degree is
        over DISTINCT neighbors (duplicate and already-bidirectional edge
        lists dedup first, matching triangle_count/clustering semantics)."""
        src0, dst0 = np.asarray(self.src), np.asarray(self.dst)
        keep = src0 != dst0
        lo = np.minimum(src0[keep], dst0[keep]).astype(np.int64)
        hi = np.maximum(src0[keep], dst0[keep]).astype(np.int64)
        uniq = np.unique(lo * np.int64(self.n) + hi)
        src = np.concatenate([uniq // self.n, uniq % self.n]).astype(np.int64)
        dst = np.concatenate([uniq % self.n, uniq // self.n]).astype(np.int64)
        alive = np.ones(self.n, bool)
        limit = max_iterations or self.n
        for _ in range(limit):
            live_edge = alive[src] & alive[dst]
            deg = np.bincount(dst[live_edge], minlength=self.n)
            nxt = alive & (deg >= k)
            if (nxt == alive).all():
                break
            alive = nxt
        return alive

    def clustering_coefficient(self) -> np.ndarray:
        """float[n] local clustering coefficient (``LocalClusteringCoefficient``
        analog): triangles through v / (deg(v) choose 2)."""
        g = self.undirected()
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        adj: dict = {}
        for s, d in zip(src.tolist(), dst.tolist()):
            adj.setdefault(s, set()).add(d)
        tri = np.zeros(g.n, np.int64)
        for v, nbrs in adj.items():
            t = 0
            for u in nbrs:
                t += len(nbrs & adj.get(u, set()))
            tri[v] = t // 2
        deg = np.asarray([len(adj.get(v, ())) for v in range(g.n)])
        denom = deg * (deg - 1) / 2
        with np.errstate(divide="ignore", invalid="ignore"):
            cc = np.where(denom > 0, tri / np.maximum(denom, 1), 0.0)
        return cc

    _BFS_INF = np.iinfo(np.int32).max

    def _bfs_propagate(self, init: np.ndarray, directed: bool,
                       max_supersteps: int, mesh=None) -> np.ndarray:
        """Shared BFS superstep (min-combine hop propagation) over any
        init shape — [n] for ``bfs_levels``, [n, n] for the simultaneous
        all-pairs variant; -1 marks unreachable."""
        inf = self._BFS_INF

        def msg(vals, _w):
            return jnp.where(vals < inf, vals + 1, inf)

        def update(vals, combined):
            return jnp.minimum(vals, combined).astype(jnp.int32)

        g = self if directed else self.undirected()
        out = g.scatter_gather(
            init, msg, "min", update, max_supersteps or self.n,
            converged=lambda a, b: bool(jnp.array_equal(a, b)), mesh=mesh)
        return np.where(out >= inf, -1, out).astype(np.int32)

    def bfs_levels(self, sources: "np.ndarray | int",
                   max_supersteps: int = 0,
                   directed: bool = False, mesh=None) -> np.ndarray:
        """int32[n] hop distance from the nearest source (multi-source BFS);
        unreachable = -1.  Default treats edges as undirected;
        ``directed=True`` follows edge direction only (matching ``sssp``,
        which always runs on the directed edges)."""
        srcs = np.atleast_1d(np.asarray(sources, np.int64))
        init = np.full(self.n, self._BFS_INF, np.int32)
        init[srcs] = 0
        return self._bfs_propagate(init, directed, max_supersteps, mesh)

    def label_propagation(self, initial_labels: np.ndarray,
                          num_iterations: int = 10) -> np.ndarray:
        """Community detection by iterated max-label adoption
        (``LabelPropagation`` analog, deterministic max tie-break)."""
        g = self.undirected()

        def msg(vals, _w):
            return vals

        def update(vals, combined):
            # adopt the max neighbor label (0 in-degree keeps its own)
            has_nb = combined > jnp.iinfo(jnp.int32).min
            return jnp.where(has_nb, jnp.maximum(vals, combined), vals)

        return g.scatter_gather(
            jnp.asarray(initial_labels, jnp.int32), msg, "max", update,
            num_iterations,
            converged=lambda a, b: bool(jnp.array_equal(a, b)))

    def hits(self, num_iterations: int = 20
             ) -> Tuple[np.ndarray, np.ndarray]:
        """-> (hubs, authorities), L2-normalized (``HITS`` analog): one
        jitted step does both segment-sums per iteration."""
        n = self.n

        @jax.jit
        def step(hub):
            auth = jax.ops.segment_sum(hub[self.src], self.dst, n)
            auth = auth / jnp.maximum(jnp.linalg.norm(auth), 1e-12)
            hub2 = jax.ops.segment_sum(auth[self.dst], self.src, n)
            return hub2 / jnp.maximum(jnp.linalg.norm(hub2), 1e-12), auth

        hub = jnp.ones(n, jnp.float32) / jnp.sqrt(jnp.maximum(n, 1))
        auth = hub
        for _ in range(num_iterations):
            hub, auth = step(hub)
        return np.asarray(hub), np.asarray(auth)

    # one source of truth for the similarity kernels' neighborhood views:
    # the dense/sparse split, symmetrization, and self-loop policy must
    # stay identical across jaccard_similarity / adamic_adar
    _DENSE_LIMIT = 4096

    def _dense_undirected_adjacency(self) -> np.ndarray:
        """Symmetric 0/1 adjacency with a zero diagonal (n <= _DENSE_LIMIT
        — the MXU-native matmul representation)."""
        a = np.zeros((self.n, self.n), np.float32)
        a[np.asarray(self.src), np.asarray(self.dst)] = 1.0
        a[np.asarray(self.dst), np.asarray(self.src)] = 1.0
        np.fill_diagonal(a, 0.0)
        return a

    def _undirected_neighbor_sets(self) -> dict:
        """vertex -> set of neighbors (self-loops dropped) — the sparse
        twin of :meth:`_dense_undirected_adjacency`."""
        adj: dict = {}
        for s, d in zip(np.asarray(self.src).tolist(),
                        np.asarray(self.dst).tolist()):
            if s != d:
                adj.setdefault(s, set()).add(d)
                adj.setdefault(d, set()).add(s)
        return adj

    def adamic_adar(self) -> np.ndarray:
        """Per-EDGE Adamic-Adar index: sum over common neighbors w of
        ``1 / log(deg(w))`` (``AdamicAdar.java`` in Gelly's similarity
        library).  Dense path: ``A @ diag(1/log deg) @ A.T`` — two
        MXU-native matmuls; sorted-set fallback beyond 4096 vertices."""
        src_np = np.asarray(self.src)
        dst_np = np.asarray(self.dst)
        if self.n <= self._DENSE_LIMIT:
            a = self._dense_undirected_adjacency()
            deg = a.sum(axis=1)
            inv_log = np.where(deg > 1, 1.0 / np.log(np.maximum(deg, 2.0)),
                               0.0).astype(np.float32)
            aj = jnp.asarray(a)
            scores = np.asarray((aj * jnp.asarray(inv_log)[None, :]) @ aj.T)
            return scores[src_np, dst_np]
        adj = self._undirected_neighbor_sets()
        out = np.zeros(len(src_np), np.float32)
        for i, (s, d) in enumerate(zip(src_np.tolist(), dst_np.tolist())):
            commons = adj.get(s, set()) & adj.get(d, set())
            out[i] = sum(1.0 / np.log(len(adj[w]))
                         for w in commons if len(adj[w]) > 1)
        return out

    def summarize(self, vertex_labels: np.ndarray
                  ) -> Tuple["Graph", np.ndarray, np.ndarray]:
        """Structural summarization (``Summarization.java``): contract
        vertices sharing a label into one summary vertex; summary edges
        are the DISTINCT (src-label, dst-label) pairs weighted by how many
        original edges they group.  Returns ``(summary graph with edge
        counts as weights, label of each summary vertex, original-vertex
        count per summary vertex)``."""
        labels = np.asarray(vertex_labels)
        uniq, inv = np.unique(labels, return_inverse=True)
        group_sizes = np.bincount(inv, minlength=len(uniq))
        s = inv[np.asarray(self.src)]
        d = inv[np.asarray(self.dst)]
        pair = s.astype(np.int64) * len(uniq) + d
        upair, counts = np.unique(pair, return_counts=True)
        g = Graph(len(uniq), upair // len(uniq), upair % len(uniq),
                  counts.astype(np.float32))
        return g, uniq, group_sizes.astype(np.int64)

    def bipartite_projection(self, left_size: int,
                             onto_left: bool = True) -> "Graph":
        """Bipartite projection (Gelly's ``BipartiteGraph``
        ``projectionTopSimple`` analog): edges run left->right with left
        ids in ``[0, left_size)`` and right ids in ``[left_size, n)``;
        the projection connects two LEFT vertices whenever they share a
        right neighbor (or two right vertices, ``onto_left=False``),
        weighted by the number of shared neighbors.  Self-loops drop."""
        src_np = np.asarray(self.src)
        dst_np = np.asarray(self.dst)
        if onto_left:
            keys, others, size = dst_np - left_size, src_np, left_size
        else:
            keys, others, size = src_np, dst_np - left_size, self.n - left_size
        nkeys = (self.n - left_size) if onto_left else left_size
        if size <= self._DENSE_LIMIT and nkeys <= self._DENSE_LIMIT:
            # shared-neighbor counts = B.T @ B on the biadjacency matrix —
            # the same MXU-native kernel as the similarity methods; strict
            # upper triangle keeps (u < v) pairs once, no self-loops
            b = np.zeros((nkeys, size), np.float32)
            b[keys, others] = 1.0
            counts = np.asarray(jnp.asarray(b).T @ jnp.asarray(b))
            es, ed = np.nonzero(np.triu(counts, k=1))
            return Graph(size, es.astype(np.int64), ed.astype(np.int64),
                         counts[es, ed].astype(np.float32))
        pairs: dict = {}
        by_key: dict = {}
        for k, v in zip(keys.tolist(), others.tolist()):
            by_key.setdefault(k, []).append(v)
        for members in by_key.values():
            ms = sorted(set(members))
            for i, u in enumerate(ms):
                for v in ms[i + 1:]:
                    pairs[(u, v)] = pairs.get((u, v), 0) + 1
        if not pairs:
            return Graph(size, np.empty(0, np.int64), np.empty(0, np.int64),
                         np.empty(0, np.float32))
        es = np.asarray([p[0] for p in pairs], np.int64)
        ed = np.asarray([p[1] for p in pairs], np.int64)
        w = np.asarray(list(pairs.values()), np.float32)
        return Graph(size, es, ed, w)

    def vertex_metrics(self) -> dict:
        """Aggregate graph metrics (``VertexMetrics.java``): vertex/edge
        counts, average degree, max degree, and the number of vertices
        with at least one edge."""
        deg = self.out_degrees() + self.in_degrees()
        return {
            "vertices": self.n,
            "edges": self.num_edges,
            "average_degree": float(deg.mean()) if self.n else 0.0,
            "max_degree": int(deg.max()) if self.n else 0,
            "vertices_with_edges": int((deg > 0).sum()),
        }

    def all_pairs_distances(self, directed: bool = False,
                            max_supersteps: int = 0,
                            mesh=None) -> np.ndarray:
        """int32[n, n] hop distances (``d[i, j]`` = hops from i to j,
        -1 = unreachable) — ALL sources propagate simultaneously as one
        [n, n] vertex-value matrix through the same scatter-gather
        superstep (one segment-min per step instead of n BFS runs; the
        TPU-native cut for the all-pairs family).  n² memory: sized for
        the analysis-scale graphs the eccentricity/closeness family
        targets."""
        init = np.full((self.n, self.n), self._BFS_INF, np.int32)
        np.fill_diagonal(init, 0)
        out = self._bfs_propagate(init, directed, max_supersteps, mesh)
        # out[i, j] = distance from column-source j; expose row-source
        # orientation d[i, j] = i -> j
        return out.T.copy()

    def eccentricity(self, mesh=None,
                     distances: Optional[np.ndarray] = None) -> np.ndarray:
        """int32[n] eccentricity: each vertex's maximum hop distance to
        any REACHABLE vertex over the undirected graph (isolated
        vertices: 0) — the ``Eccentricity`` library analog.  Pass a
        precomputed ``all_pairs_distances()`` matrix to share one BFS
        across the eccentricity/closeness/diameter family."""
        d = (distances if distances is not None
             else self.all_pairs_distances(mesh=mesh))
        masked = np.where(d >= 0, d, 0)
        return masked.max(axis=1).astype(np.int32)

    def closeness_centrality(self, mesh=None,
                             distances: Optional[np.ndarray] = None
                             ) -> np.ndarray:
        """float32[n] closeness with the Wasserman–Faust component
        correction: ``((r-1)/(n-1)) * ((r-1)/sum_d)`` where r = reachable
        vertices (incl. self) — comparable across disconnected
        components; isolated vertices score 0."""
        d = (distances if distances is not None
             else self.all_pairs_distances(mesh=mesh))
        reach = (d >= 0).sum(axis=1)                  # includes self (d=0)
        dist_sum = np.where(d > 0, d, 0).sum(axis=1)
        r1 = (reach - 1).astype(np.float64)
        denom = np.maximum(dist_sum, 1)
        frac = np.where(dist_sum > 0, r1 / denom, 0.0)
        scale = r1 / max(self.n - 1, 1)
        return (scale * frac).astype(np.float32)

    def diameter_radius(self, mesh=None,
                        distances: Optional[np.ndarray] = None) -> dict:
        """Graph diameter/radius over the undirected graph's non-isolated
        vertices.  Self-loops do not make a vertex non-isolated (they
        contribute no path to anywhere else, like the triangle/k-core
        paths that drop them)."""
        ecc = self.eccentricity(mesh=mesh, distances=distances)
        src_np = np.asarray(self.src)
        dst_np = np.asarray(self.dst)
        real = src_np != dst_np                  # ignore self-loops
        deg = np.zeros(self.n, np.int64)
        np.add.at(deg, src_np[real], 1)
        np.add.at(deg, dst_np[real], 1)
        live = ecc[deg > 0]
        if live.size == 0:
            return {"diameter": 0, "radius": 0}
        return {"diameter": int(live.max()), "radius": int(live.min())}

    def jaccard_similarity(self) -> np.ndarray:
        """Per-EDGE Jaccard index |N(u) ∩ N(v)| / |N(u) ∪ N(v)| over the
        undirected neighborhood (``JaccardIndex`` analog).  Dense
        adjacency matmul (the MXU-native kernel) for n <= 4096; sorted
        set intersection beyond."""
        src_np = np.asarray(self.src)
        dst_np = np.asarray(self.dst)
        if self.n <= self._DENSE_LIMIT:
            a = self._dense_undirected_adjacency()
            common = np.asarray(
                jnp.asarray(a) @ jnp.asarray(a).T)[src_np, dst_np]
            deg = a.sum(axis=1)
            union = deg[src_np] + deg[dst_np] - common
            return np.where(union > 0, common / np.maximum(union, 1.0), 0.0)
        adj = self._undirected_neighbor_sets()
        out = np.zeros(len(src_np), np.float32)
        for i, (s, d) in enumerate(zip(src_np.tolist(), dst_np.tolist())):
            ns, nd = adj.get(s, set()), adj.get(d, set())
            inter = len(ns & nd)
            union = len(ns | nd)
            out[i] = inter / union if union else 0.0
        return out
