"""Production recommendation pipeline — feature-fetch -> exact MXU top-k
recall -> ranking as ONE path through the multi-tenant serving engine.

Reference analog (unverified — mount empty): ``scala/friesian``'s
Recommender gRPC service chains the feature/recall/ranking microservices
over the network (SURVEY.md §3.4).  TPU-native re-design: both model
stages live in ONE :class:`~bigdl_tpu.serving.server.ServingServer` as
separate tenants — recall and ranking each get their own bounded queue,
SLO burn accounting, and degradation state (docs/serving.md §Multi-tenant
serving), while sharing the engine's predict loop.  The recall stage is
admitted normally (it competes with other tenants under weighted
admission); the candidate batch it produces flows straight into the
ranking tenant via :meth:`ServingServer.predict_inline` WITHOUT
re-entering admission — an accepted recommend request is never shed
halfway through by its own second stage.

Embedding tables serve mesh-sharded: pass ``layout="fsdp:2,tp:4"`` (any
``parallelism=`` combo string, docs/parallelism.md §Declarative layouts)
and both stage models shard their TwoTower parameters over the mesh via
the registered ``two_tower_layout`` table — the id-embedding tables are
vocab-sharded over fsdp x tp, so per-chip table bytes shrink by the
model-shard factor.  The sparse lookup collectives this implies are
priced by :func:`~bigdl_tpu.parallel.layout.embedding_lookup_bytes`
(surfaced through :meth:`RecommendationPipeline.lookup_collective_bytes`).

Compile discipline: both stages run on CLOSED bucket sets
(``batch_buckets`` here; candidate count is a static shape), and
:meth:`warmup` compiles every program under ``expected_compile`` — a
mixed-size recommend sweep is zero unexpected recompiles under the
recompile sentinel (docs/observability.md §Recompile sentinel).
"""

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bigdl_tpu.friesian.serving import FeatureService
from bigdl_tpu.parallel.layout import register_layout, two_tower_layout
from bigdl_tpu.serving.inference_model import InferenceModel
from bigdl_tpu.serving.server import ServingConfig, ServingServer

_HELP = {
    "serving.recsys.feature_s": "recommend feature-fetch stage latency "
                                "(user history lookup)",
    "serving.recsys.recall_s": "recommend recall stage latency (tenant "
                               "admission + MXU top-k)",
    "serving.recsys.rank_s": "recommend ranking stage latency (inline "
                             "candidate scoring, no re-admission)",
    "serving.recsys.recommend_s": "end-to-end recommend latency across "
                                  "all three stages",
    "serving.recsys.candidates": "recall candidates handed to ranking "
                                 "per recommend request",
    "serving.recsys.requests": "recommend requests completed by the "
                               "pipeline",
}


class RecallTopKModel:
    """Recall stage as an InferenceModel-servable module: encode the user
    query tower, score it against EVERY item tower output on the MXU, and
    return the static-shape top-k — ``(B, 2k)`` float32 rows laid out as
    ``scores ‖ ids`` so the candidate batch survives the engine's
    row-splitting result path unchanged.

    Input rows are ``(B, 1+H)`` float32: user id then H history item ids
    (0 = padding, the TwoTower convention)."""

    def __init__(self, two_tower, n_items: int, k: int):
        self.two_tower = two_tower
        self.n_items = int(n_items)
        self.k = int(k)
        if self.k > self.n_items:
            raise ValueError(f"k ({self.k}) > n_items ({self.n_items})")

    def forward(self, params, state, x, training: bool = False):
        import jax
        import jax.numpy as jnp

        uid = x[:, 0].astype(jnp.int32)
        hist = x[:, 1:].astype(jnp.int32)
        q = self.two_tower.encode_users(params, uid, hist)
        items = jnp.arange(self.n_items, dtype=jnp.int32)
        v = self.two_tower.encode_items(params, items)
        scores = jnp.matmul(q, v.T, preferred_element_type=jnp.float32)
        top, idx = jax.lax.top_k(scores, self.k)
        out = jnp.concatenate([top, idx.astype(jnp.float32)], axis=1)
        return out, state


class RankTowerModel:
    """Ranking stage: score one (user, candidate-item) pair per row as the
    two-tower dot product.  Input rows are ``(B, 1+H+1)`` float32 — user
    id, H history ids, candidate item id; output ``(B, 1)`` scores."""

    def __init__(self, two_tower):
        self.two_tower = two_tower

    def forward(self, params, state, x, training: bool = False):
        import jax.numpy as jnp

        uid = x[:, 0].astype(jnp.int32)
        hist = x[:, 1:-1].astype(jnp.int32)
        iid = x[:, -1].astype(jnp.int32)
        u = self.two_tower.encode_users(params, uid, hist)
        v = self.two_tower.encode_items(params, iid)
        out = jnp.sum(u * v, axis=-1, keepdims=True)
        return out, state


# both wrappers carry raw TwoTower params (user_emb/item_emb/[ui]w*/..),
# so the two-tower layout table shards them — the id tables land
# vocab-sharded over fsdp x tp exactly as in training
register_layout("RecallTopKModel", two_tower_layout)
register_layout("RankTowerModel", two_tower_layout)


class RecommendationPipeline:
    """feature-fetch -> recall tenant -> inline ranking, one engine.

    ``server=None`` builds and owns a private :class:`ServingServer`
    (started lazily on first use, stopped by :meth:`stop`); pass a running
    server to co-tenant with other workloads.  ``layout=`` serves BOTH
    stage models mesh-sharded (a ``parallelism=`` combo string or a
    ResolvedLayout)."""

    def __init__(self, two_tower, params: Dict[str, Any],
                 feature_service: FeatureService, *, hist_len: int,
                 n_items: Optional[int] = None, k_candidates: int = 64,
                 layout=None, server: Optional[ServingServer] = None,
                 config: Optional[ServingConfig] = None,
                 batch_buckets: Sequence[int] = (1, 4, 16, 64),
                 recall_tenant: str = "recall",
                 ranking_tenant: str = "ranking",
                 user_namespace: str = "user_hist"):
        if n_items is None:
            n_items = int(np.asarray(params["item_emb"]).shape[0])
        self.two_tower = two_tower
        self.params = params
        self.hist_len = int(hist_len)
        self.n_items = int(n_items)
        self.k_candidates = int(min(k_candidates, n_items))
        self.features = feature_service
        self.user_ns = user_namespace
        self.recall_tenant = recall_tenant
        self.ranking_tenant = ranking_tenant
        self.layout = layout

        self.recall_model = InferenceModel(
            RecallTopKModel(two_tower, self.n_items, self.k_candidates),
            {"params": params}, batch_buckets=tuple(batch_buckets),
            layout=layout)
        self.ranking_model = InferenceModel(
            RankTowerModel(two_tower), {"params": params},
            batch_buckets=tuple(batch_buckets), layout=layout)

        self._own_server = server is None
        if server is None:
            server = ServingServer(
                config=config or ServingConfig(),
                models={recall_tenant: self.recall_model,
                        ranking_tenant: self.ranking_model})
        else:
            server.register_model(recall_tenant, self.recall_model)
            server.register_model(ranking_tenant, self.ranking_model)
        self.server = server
        self.metrics = server.metrics
        for name, help_text in _HELP.items():
            self.metrics.describe(name, help_text)
        self._started = False
        self._start_lock = threading.Lock()

    # -- lifecycle ----------------------------------------------------------

    def _ensure_started(self) -> None:
        if self._started:
            return
        with self._start_lock:
            if not self._started:
                if self._own_server:
                    self.server.start()
                self._started = True

    def start(self) -> "RecommendationPipeline":
        self._ensure_started()
        return self

    def stop(self) -> None:
        if self._own_server and self._started:
            self.server.stop()
        self._started = False

    def warmup(self) -> "RecommendationPipeline":
        """Compile every bucket of both stage programs under
        ``expected_compile`` — after this the serving path never traces."""
        self.recall_model.warmup(
            np.zeros((1, 1 + self.hist_len), np.float32))
        self.ranking_model.warmup(
            np.zeros((1, 1 + self.hist_len + 1), np.float32))
        return self

    # -- features -----------------------------------------------------------

    def put_user_history(self, user_id: int, hist) -> None:
        """Store a user's item-id history (padded/truncated to
        ``hist_len``; 0 = padding per the TwoTower convention)."""
        hist = np.asarray(hist, np.int64).ravel()[:self.hist_len]
        if hist.shape[0] < self.hist_len:
            hist = np.concatenate(
                [hist, np.zeros(self.hist_len - hist.shape[0], np.int64)])
        self.features.put(self.user_ns, int(user_id), hist)

    def _user_row(self, user_id) -> np.ndarray:
        hist = self.features.get(self.user_ns, int(user_id))
        if hist is None:
            raise KeyError(f"unknown user {user_id!r}")
        return np.concatenate([[float(user_id)],
                               np.asarray(hist, np.float32)])

    # -- the serving path ---------------------------------------------------

    def recommend(self, user_id, k: int = 10,
                  deadline_s: Optional[float] = None,
                  request_id: Optional[str] = None
                  ) -> List[Tuple[int, float]]:
        """Top-``k`` (item_id, score) for ``user_id`` through the full
        pipeline.  The recall stage is admitted to its tenant queue (it
        can shed under load like any tenant); the candidate batch is then
        ranked inline on this thread without re-entering admission."""
        self._ensure_started()
        t0 = time.time()
        user = self._user_row(user_id)          # feature stage
        t1 = time.time()
        rid = self.server.enqueue(user[None].astype(np.float32),
                                  request_id=request_id,
                                  deadline_s=deadline_s,
                                  model=self.recall_tenant)
        out = np.asarray(self.server.query(
            rid, timeout=deadline_s if deadline_s is not None else 30.0))
        kc = self.k_candidates
        scores = out[0, :kc]
        ids = out[0, kc:].astype(np.int64)
        t2 = time.time()
        rows = np.concatenate(
            [np.repeat(user[None], kc, axis=0), ids[:, None]],
            axis=1).astype(np.float32)
        ranked = np.asarray(
            self.server.predict_inline(self.ranking_tenant, rows)
        ).reshape(kc)
        t3 = time.time()
        # rank scores order the final list; recall (inner-product) scores
        # are a different scale and are never mixed in as comparable
        order = np.argsort(-ranked)[:min(k, kc)]
        m = self.metrics
        m.observe("serving.recsys.feature_s", t1 - t0)
        m.observe("serving.recsys.recall_s", t2 - t1)
        m.observe("serving.recsys.rank_s", t3 - t2)
        m.observe("serving.recsys.recommend_s", t3 - t0)
        m.observe("serving.recsys.candidates", float(kc))
        m.inc("serving.recsys.requests")
        _ = scores  # recall scores kept for parity checks via recall_only
        return [(int(ids[i]), float(ranked[i])) for i in order]

    def recall_only(self, user_id) -> Tuple[np.ndarray, np.ndarray]:
        """The recall stage alone: (scores, candidate ids) — the parity
        and bench hook (byte-level comparisons need the raw arrays)."""
        self._ensure_started()
        user = self._user_row(user_id)
        rid = self.server.enqueue(user[None].astype(np.float32),
                                  model=self.recall_tenant)
        out = np.asarray(self.server.query(rid))
        kc = self.k_candidates
        return out[0, :kc], out[0, kc:].astype(np.int64)

    # -- sharding ledger ----------------------------------------------------

    def lookup_collective_bytes(self) -> Dict[str, Any]:
        """Price the sparse embedding-lookup collectives of ONE recommend
        batch in the per-axis ledger (docs/parallelism.md §Reading the
        ledger): a vocab-sharded gather all-gathers the looked-up rows
        over each shard axis.  Unsharded serving prices to zero."""
        from bigdl_tpu.parallel.layout import embedding_lookup_bytes

        resolved = self.recall_model.layout
        dim = int(np.asarray(
            self.recall_model._params["item_emb"]).shape[-1])
        sizes = dict(getattr(resolved, "sizes", {}) or {}) if resolved \
            else {}
        # per recommend: 1 user-emb row + hist_len history rows +
        # k_candidates item rows through the ranking tower (the recall
        # scan reads the whole table locally — no gather)
        return embedding_lookup_bytes(
            batch=1 + self.hist_len + self.k_candidates, dim=dim,
            sizes=sizes, n_tables=1)

    def param_bytes_per_chip(self) -> Dict[str, int]:
        """Measured per-chip bytes of the two id-embedding tables as
        actually placed — the sharded-serving acceptance number."""
        out = {}
        for name in ("user_emb", "item_emb"):
            arr = self.recall_model._params.get(name)
            if arr is None:
                continue
            shards = getattr(arr, "addressable_shards", None)
            out[name] = (int(shards[0].data.nbytes) if shards
                         else int(np.asarray(arr).nbytes))
        return out
