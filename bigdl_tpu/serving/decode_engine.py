"""Token-level continuous batching — paged KV-cache decode engine.

ROADMAP item 3: the continuous engine (PR 8) batches *stateless*
predicts; autoregressive generation is stateful — a sequence occupies
its seat for many model steps.  The r05-era answer (``seq2seq.py``'s
one-``lax.scan`` whole-batch decode) holds every seat until the LAST
row finishes: one long request stalls the whole batch, and a request
arriving mid-decode waits for a full batch restart.  This engine runs
generation ONE MODEL STEP AT A TIME over a fixed pool of sequence
slots:

- **Paged KV cache** — decoder self-attention K/V live in a pool of
  fixed-size pages (``page_size`` tokens each); a slot owns an ordered
  page list, so a finished sequence returns its pages mid-flight and a
  queued request reuses them on the next step (vLLM-style paging, at
  the block granularity the TPU memory system likes).
- **Closed compile set** — every jitted program is keyed by a bucketed
  cache length (pages doubling up to the slot cap) and the fixed chunk
  size, all pre-compilable by :meth:`DecodeEngine.warmup` under
  ``expected_compile``; a mixed prompt/generation-length sweep triggers
  ZERO unexpected XLA recompiles (the PR 6 sentinel discipline).
- **In-flight insertion / eviction at step granularity** — admission is
  re-evaluated between steps from a (deadline, seq) heap (the PR 8
  per-tenant deadline ordering); a finished or expired sequence frees
  its slot and pages immediately and the next queued request claims
  them on the following step.  Deadlines are re-checked per token, so
  an expired streaming request never decodes to ``max_new_tokens``.
- **Prefill/decode separation** — prompts chunk through a prefill
  program (``prompt_chunk`` tokens per call, attending over the pages
  written so far) interleaved one chunk per engine iteration with
  decode steps, so a long prompt never stalls the decode batch; the
  decode program only ever runs query-length-1 steps.

Byte-identical parity (the acceptance invariant): the continuous
engine's tokens are byte-identical to :meth:`DecodeEngine.
static_generate` — the one-scan whole-sequence reference — for the
same request set, greedy AND seeded-sample, including requests
inserted mid-flight.  The two paths share ``chunk_forward`` (the layer
math) and ``_select_tokens`` (the sampling rule) verbatim; parity then
rests on three XLA facts the test suite pins: per-row results of a
matmul are independent of the number of co-batched rows (for >= 2
rows — single-row programs take a different gemv path, so every
matmul in both paths keeps >= 2 rows), masked-softmax attention is
bit-stable under padded key lengths (masked lanes contribute exact
zeros), and threefry key streams are counter-based (per-row
``fold_in(request_key, position)`` draws are batch-shape-independent).

Speculative decoding (docs/serving.md §Speculative decoding):
``DecodeConfig.speculative=SpecConfig(k, sparsity)`` swaps the
one-token decode step for a draft+verify iteration — a block-sparse
twin of the SAME checkpoint (weights shared verbatim, only the FFN
block masks differ; BLaST lineage, ops/block_sparse.py) drafts ``k``
tokens against its own float32 KV pages, then ONE target verify
program of query length ``k+1`` scores the whole chunk and the host
accepts the longest agreeing prefix.  Every emitted token is a TARGET
selection, so greedy output is byte-identical to the spec-off engine
and to :meth:`DecodeEngine.static_generate` by construction, and
temperature>0 keeps seeded parity because draft and verify share
``_select_tokens``'s counter-based key streams (the shared-Gumbel
coupling also makes a close draft agree often).  Draft pages live in a
parallel f32 pool indexed by the SAME page table, so cancel/expiry/
migration free draft state together with target state structurally.

Observability: ``serving.decode.*`` gauges/histograms — tokens/s,
time-to-first-token, inter-token latency, slot occupancy, page
utilization, speculation acceptance — all described in
``obs/export.py``'s catalog (docs/serving.md §Autoregressive decode
has the knob table).
"""

import heapq
import itertools
import math
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import _attn_project, positional_encoding
from bigdl_tpu.nn.module import EMPTY
from bigdl_tpu.obs import flight, trace
from bigdl_tpu.resilience import faults
from bigdl_tpu.utils.log import get_logger

log = get_logger("bigdl_tpu.serving.decode")

_NEG_INF = -1e30


class RequestCancelledError(RuntimeError):
    """A request was cancelled before completing — its client went away
    (``reason="client_disconnect"``) or its live slot was migrated to a
    peer worker during a drain (``reason="migrated"``).  Carries the
    request id and reason so the HTTP frontend can pick the right
    framing: a disconnected client gets nothing (it's gone), a migrated
    stream is aborted WITHOUT the chunked terminator so the pool proxy
    detects truncation and fails the stream over."""

    def __init__(self, rid: str, reason: str):
        super().__init__(f"request {rid} cancelled: {reason}")
        self.rid = rid
        self.reason = reason


# ---------------------------------------------------------------------------
# config / request / result
# ---------------------------------------------------------------------------

@dataclass
class SpecConfig:
    """Speculative-decoding knobs (docs/serving.md §Speculative
    decoding).  The draft is ALWAYS the served checkpoint itself with
    block-sparse FFNs — no second model, no distillation; ``sparsity``
    trades draft speed against acceptance rate (0.0 = a dense twin:
    acceptance 1.0, no speedup — the accounting-test configuration)."""

    k: int = 4                 # tokens drafted per engine iteration
    sparsity: float = 0.5      # FFN block sparsity of the draft twin
    sparse_block: Tuple[int, int] = (8, 8)
    # "auto" = the Pallas block-sparse kernel on TPU, masked-dense jnp
    # elsewhere (a grid launch per FFN costs more than the skipped
    # FLOPs at CPU-test sizes); "kernel"/"masked" force a path
    draft_impl: str = "auto"
    # How the target scores the drafted chunk (docs/serving.md
    # §Speculative decoding — "Two verify tracings"):
    #   "scan"  — k+1 single-token steps mirroring the decode step
    #             op-for-op under one lax.scan: ONE dispatch, byte
    #             parity (tokens AND logp) with spec-off output.
    #   "chunk" — one multi-query pass over the chunk (query length
    #             k+1): collapses the per-step op count ~(k+1)x, the
    #             perf configuration.  Token-stream parity holds (the
    #             selections agree); logp is allclose-not-bitwise —
    #             the same contract as spec-off flash decode.  f32 KV
    #             only (int8 RMW is inherently per-position).
    #   "auto"  — "chunk" where the flash kernel runs (TPU), "scan"
    #             elsewhere: byte parity wherever the platform has it.
    verify_impl: str = "auto"
    # Draft attention window: None = the draft attends its full
    # context (exactly like the target); an int W = the draft scan
    # attends only the last W positions through a ring buffer carried
    # across the k+1 steps.  At long contexts this caps the draft's
    # per-step attention traffic at O(W) while the target re-reads the
    # whole cache — the verify is still exact over the full context,
    # so output parity is untouched; only the acceptance rate moves.
    draft_window: Optional[int] = None


@dataclass
class DecodeConfig:
    """Engine geometry.  ``slots * pages_per_slot`` pages exist by
    default; ``page_size * pages_per_slot`` is the per-sequence token
    cap (prompt + generated).  All sizes are static — they define the
    closed set of compiled programs."""

    slots: int = 8
    page_size: int = 16
    pages_per_slot: int = 8
    # total pages in the pool; None = slots * pages_per_slot (admission
    # then never blocks on pages).  Smaller values exercise page-level
    # admission control: a request is only admitted when its WORST-CASE
    # page need is reservable, so a slot can never starve mid-flight.
    num_pages: Optional[int] = None
    # prefill chunk length: prompts run through the prefill program
    # this many tokens at a time, one prefill CALL per engine iteration
    prompt_chunk: int = 16
    # slots co-batched per prefill call (padded to exactly this many
    # rows — one compiled program, and >= 2 rows keeps the bit-parity
    # rule).  Batching amortizes the per-dispatch host cost that would
    # otherwise make admission-heavy traffic prefill-bound
    prefill_batch: int = 4
    max_new_tokens: int = 32          # default per-request cap
    eos_id: int = 1
    base_seed: int = 0
    # False = whole-batch-restart baseline: admission only happens when
    # EVERY slot is free, and each wave decodes the FULL
    # ``max_new_tokens`` horizon before any seat frees — the cost model
    # of the legacy one-``lax.scan`` whole-sequence decode this engine
    # replaces (a fixed-length scan cannot exit early; a finished row
    # holds its seat to the last step).
    continuous: bool = True
    queue_capacity: int = 4096
    # None = auto (Pallas kernel on TPU, gathered-jnp path elsewhere).
    # The jnp path is the byte-parity reference; the kernel path is the
    # TPU production path (allclose, not bitwise — online softmax).
    use_flash_decode: Optional[bool] = None
    # prefix/KV-cache reuse (docs/serving.md §Decode fleet): completed
    # cold requests DONATE their page-aligned prompt-prefix pages to a
    # per-engine cache (up to this many pages; 0 disables) and later
    # requests sharing the prefix attach to the cached pages instead of
    # re-prefilling them.  Continuous mode only; cached pages are
    # reclaimed (LRU, idle entries only) when admission runs short.
    prefix_cache_pages: int = 0
    # KV page storage dtype (docs/quantization.md §Serving memory
    # hierarchy): "float32" (the byte-parity default) or "int8" —
    # pages store int8 payloads with one abs-max scale per (layer,
    # page) riding the page table.  int8 shrinks page HBM ~4x (so a
    # fixed HBM budget holds ~2x the decode slots once weights are
    # quantized too) at the cost of relaxing byte parity to the
    # token-parity budget (greedy token agreement + bounded logp
    # drift) asserted in tests/test_quant_serving.py.
    kv_dtype: str = "float32"
    # speculative decoding (docs/serving.md §Speculative decoding):
    # a SpecConfig turns every decode iteration into draft(k)+verify —
    # continuous LM engines only.  Greedy output stays byte-identical
    # to speculative=None; the f32 draft page pool roughly doubles the
    # per-page HBM cost (see kv_bytes_per_page).
    speculative: Optional[SpecConfig] = None

    @property
    def cap(self) -> int:
        return self.page_size * self.pages_per_slot

    @property
    def total_pages(self) -> int:
        return self.num_pages if self.num_pages is not None \
            else self.slots * self.pages_per_slot

    def len_buckets(self) -> Tuple[int, ...]:
        """Cache-length buckets in PAGES: doubling from 1 up to the slot
        cap — the closed set every decode/prefill program is keyed by."""
        out = []
        b = 1
        while b < self.pages_per_slot:
            out.append(b)
            b *= 2
        out.append(self.pages_per_slot)
        return tuple(out)

    def bucket_pages(self, tokens: int) -> int:
        """Smallest bucket (in pages) covering ``tokens`` cache slots.
        Floored so the attended width is >= 8 keys: XLA's tiny-reduce
        path for a narrower masked softmax is not bit-stable against
        the wider buckets (measured; docs/serving.md §Autoregressive
        decode), and the parity invariant is non-negotiable."""
        need = max(1, -(-max(tokens, 8) // self.page_size))
        for b in self.len_buckets():
            if b >= need:
                return b
        return self.pages_per_slot


@dataclass
class DecodeRequest:
    """One generation request.  ``tokens`` is the prompt (for seq2seq:
    the SOURCE sequence — the adapter turns it into encoder context and
    a BOS decoder prompt)."""

    tokens: np.ndarray
    max_new_tokens: Optional[int] = None
    temperature: float = 0.0          # 0 = greedy
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    rid: Optional[str] = None
    tenant: str = "default"
    deadline_t: float = math.inf      # absolute; math.inf = never
    on_token: Optional[Callable[[str, int, int], None]] = None
    on_done: Optional[Callable[["DecodeRequest"], None]] = None
    # -- fleet prefill/decode split (docs/serving.md §Decode fleet) ---------
    # export_kv: run as a PREFILL-ONLY request (pair with
    # max_new_tokens=1): on completion the slot's prompt KV pages are
    # copied to host and stashed on ``kv_export`` for
    # fleet.handoff.pack_handoff.  handoff: admit a request whose
    # prefill ran on another worker — the unpacked handoff dict; the
    # engine scatters the transferred pages and continues decoding from
    # the handoff's first token, byte-identical to a local prefill.
    export_kv: bool = False
    handoff: Optional[dict] = None
    # -- engine-internal ----------------------------------------------------
    kv_export: Optional[dict] = None   # filled by the export_kv path
    admit_t: float = 0.0
    seq: int = 0
    prepared: Optional[tuple] = None   # cached adapter.prepare() output
    result: Optional["DecodeResult"] = None
    error: Optional[Exception] = None
    _event: threading.Event = field(default_factory=threading.Event,
                                    repr=False)

    def wait(self, timeout: Optional[float] = None) -> "DecodeResult":
        if not self._event.wait(timeout):
            raise TimeoutError(f"decode request {self.rid} not done")
        if self.error is not None:
            raise self.error
        return self.result


@dataclass
class DecodeResult:
    tokens: np.ndarray        # generated tokens, EOS included if hit
    logp: float               # summed log-prob of the generated tokens
    prompt_len: int
    ttft_s: float             # admission -> first token
    finish_reason: str        # "eos" | "length" | "expired"


class _ActiveSeq:
    """Host-side state of one occupied slot."""

    __slots__ = ("req", "prompt", "ctx", "pages", "reserved",
                 "generated", "logp", "first_logp", "last_logp",
                 "prefill_pos", "shared", "shared_entry",
                 "first_token_t", "last_token_t", "max_new", "done",
                 "frozen")

    def __init__(self, req: DecodeRequest, prompt: np.ndarray, ctx,
                 reserved: int, max_new: int):
        self.req = req
        self.prompt = prompt
        self.ctx = ctx
        self.pages: List[int] = []    # pages this slot OWNS (rows after
        #                               any shared prefix-cache rows)
        self.reserved = reserved      # owned pages reserved, not yet taken
        self.generated: List[int] = []
        self.logp = np.float32(0.0)
        self.first_logp = np.float32(0.0)
        self.last_logp = np.float32(0.0)
        self.prefill_pos = 0          # prompt tokens consumed by prefill
        self.shared: List[int] = []   # prefix-cache pages mapped read-only
        self.shared_entry = None      # the cache entry holding our ref
        self.first_token_t = 0.0
        self.last_token_t = 0.0
        self.max_new = max_new
        self.done = False
        self.frozen = False   # migration export taken; no more decoding

    @property
    def prefilling(self) -> bool:
        return self.prefill_pos < len(self.prompt)


# ---------------------------------------------------------------------------
# shared math: token selection (greedy / temperature / top-k / top-p)
# ---------------------------------------------------------------------------

def _select_tokens(logits, keys, positions, temps, top_ks, top_ps):
    """Per-row next-token selection — THE sampling rule both the
    continuous engine and the static reference trace, so they agree to
    the bit.  ``positions`` is the sequence position each selected token
    will occupy; the draw key is ``fold_in(request_key, position)``, a
    counter-based stream independent of batch shape and engine step
    index (the property that makes mid-flight insertion parity-safe).

    ``temps <= 0`` rows take the greedy argmax; sampling rows apply
    temperature, per-row top-k (threshold at the k-th sorted logit) and
    nucleus top-p (the standard keep-the-crossing-token rule), then an
    explicit per-row Gumbel-max draw (``categorical`` re-derived so the
    bits depend only on the row's key).  Returns ``(token, logp)`` with
    logp from the UNfiltered log-softmax."""
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    lp_full = jax.nn.log_softmax(logits, axis=-1)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _sampled(_):
        z = logits / jnp.maximum(temps, 1e-6)[:, None]
        zs = jnp.sort(z, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            zs, jnp.clip(top_ks - 1, 0, vocab - 1)[:, None], axis=-1)
        z = jnp.where((top_ks > 0)[:, None] & (z < kth), -jnp.inf, z)
        zs2 = jnp.sort(z, axis=-1)[:, ::-1]
        ps = jax.nn.softmax(zs2, axis=-1)
        prev_mass = jnp.cumsum(ps, axis=-1) - ps
        keep = prev_mass < top_ps[:, None]
        minz = jnp.min(jnp.where(keep, zs2, jnp.inf), axis=-1,
                       keepdims=True)
        z = jnp.where((top_ps < 1.0)[:, None] & (z < minz), -jnp.inf, z)

        step_keys = jax.vmap(jax.random.fold_in)(keys, positions)
        tiny = jnp.finfo(jnp.float32).tiny
        u = jax.vmap(lambda k: jax.random.uniform(
            k, (vocab,), minval=tiny, maxval=1.0))(step_keys)
        gumbel = -jnp.log(-jnp.log(u))
        return jnp.argmax(z + gumbel, axis=-1).astype(jnp.int32)

    # the sort/threefry machinery above is ~vocab-sized work PER ROW;
    # all-greedy batches (temps <= 0 everywhere) never read its result,
    # so gate it behind a runtime cond — with any sampling row present
    # the exact same ops run, so the bits never change
    sampled_tok = jax.lax.cond(jnp.any(temps > 0.0), _sampled,
                               lambda _: greedy_tok, None)

    tok = jnp.where(temps <= 0.0, greedy_tok, sampled_tok)
    logp = jnp.take_along_axis(lp_full, tok[:, None], axis=-1)[:, 0]
    return tok, logp


def _write_chunk(buf, positions, new, cap):
    """Scatter ``new`` (B, h, C, hd) into ``buf`` (B, h, K, hd) at
    per-row positions ``positions + [0..C)``; out-of-range positions
    (padded chunk tails crossing the cap) are dropped."""
    B, _, C, _ = new.shape
    rows = jnp.arange(B)[:, None]
    cols = positions[:, None] + jnp.arange(C)[None, :]
    cols = jnp.where(cols < cap, cols, buf.shape[2])
    return buf.at[rows, :, cols].set(
        new.transpose(0, 2, 1, 3).astype(buf.dtype), mode="drop")


# ---------------------------------------------------------------------------
# model adapters: the layer math both decode paths share
# ---------------------------------------------------------------------------

class _AdapterBase:
    """Shared transformer step math over an explicit KV buffer.  The
    engine feeds it a page-gathered view; the static reference feeds it
    a contiguous cache — identical values at every unmasked position,
    so the outputs agree bitwise (see the module docstring)."""

    def __init__(self, model, params, layout=None, weight_quant=None):
        """``layout``: serve the checkpoint MODEL-SHARDED — a
        ``parallelism=`` combo string ("tp:8") or a resolved
        :class:`~bigdl_tpu.parallel.ResolvedLayout`; every parameter is
        placed as a ``NamedSharding`` per the model's layout table
        (docs/parallelism.md §Declarative layouts) and the engine's
        jitted programs partition under GSPMD.  The closed compile set
        (cache buckets x prefill/decode programs) is unchanged.

        ``weight_quant="int8"``: store the matmul-family params int8
        with per-out-column scales (docs/quantization.md §Serving
        memory hierarchy) — 4x less HBM at rest, so one chip holds a
        bigger checkpoint.  Every adapter param access happens inside
        the engine's traced programs, so the dequantize compiles into
        each program (fused into the weight reads) and the f32 copy
        never persists between steps.  Accepts an already-quantized
        tree unchanged (the InferenceModel path quantizes once)."""
        self.layout = None
        if layout is not None:
            from bigdl_tpu.parallel.mesh_policy import (ResolvedLayout,
                                                        mesh_and_layout)

            self.layout = (layout if isinstance(layout, ResolvedLayout)
                           else mesh_and_layout(str(layout)))
            params = self.layout.shard_params(model, params)
        if weight_quant not in (None, "int8"):
            raise ValueError(
                f"weight_quant {weight_quant!r}: None | 'int8'")
        self.weight_quant = weight_quant
        if weight_quant == "int8":
            from bigdl_tpu.nn.quantized import quantize_params

            params = quantize_params(params)   # idempotent
        self.model = model
        self._params_stored = params
        self._tracing = threading.local()

    @property
    def params(self):
        """The param tree the traced step math consumes: inside a
        :meth:`jit` program the program's own ARGUMENT, the stored tree
        otherwise.  Under ``weight_quant="int8"`` each access rebuilds
        the f32 view from the stored int8 tree — cheap at trace time
        (ops, not data; XLA CSEs repeated accesses within one
        program)."""
        stored = getattr(self._tracing, "stored", None)
        if stored is None:
            stored = self._params_stored
        if self.weight_quant == "int8":
            from bigdl_tpu.nn.quantized import dequantize_params

            return dequantize_params(stored)
        return stored

    def jit(self, fn, donate_argnums=()):
        """``jax.jit(fn)`` with the stored checkpoint as a leading
        ARGUMENT of the compiled program; ``fn`` reads weights through
        :attr:`params`.  A program that closes over the concrete tree
        instead carries the whole checkpoint as a constant — 441 MB of
        StableHLO per decode step at 12 layers x d768, and one private
        copy of the weights in device memory per length bucket."""
        def with_params(stored, *args):
            self._tracing.stored = stored
            try:
                return fn(*args)
            finally:
                self._tracing.stored = None

        jitted = jax.jit(with_params, donate_argnums=tuple(
            i + 1 for i in donate_argnums))
        return lambda *args: jitted(self._params_stored, *args)

    def _split(self, x):
        b, t, _ = x.shape
        return x.reshape(b, t, self.num_heads, self.head_dim).transpose(
            0, 2, 1, 3)

    def _attend(self, q, kb, vb, valid):
        """Masked single-buffer attention: q (B,h,C,hd) over kb/vb
        (B,h,K,hd); ``valid`` (B,C,K) True = attend.  Mirrors
        ``nn.attention.transformer_decode_cached`` op-for-op."""
        hd = q.shape[-1]
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32), kb,
            preferred_element_type=jnp.float32) / jnp.sqrt(float(hd))
        logits = jnp.where(valid[:, None], logits, _NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", w, vb,
                          preferred_element_type=jnp.float32)

    def _merge(self, a, x, p):
        B, _, C, _ = a.shape
        a = a.transpose(0, 2, 1, 3).reshape(B, C,
                                            self.num_heads * self.head_dim)
        from bigdl_tpu.tensor.policy import cast_compute

        return (jnp.matmul(a.astype(x.dtype), cast_compute(p["wo"]),
                           preferred_element_type=jnp.float32)
                + p["bo"]).astype(x.dtype)

    def _logits(self, x):
        from bigdl_tpu.tensor.policy import cast_compute

        h, _ = self.model.ln_out.forward(self.params["ln_out"], EMPTY, x)
        emb = cast_compute(self.params["embedding"])
        out = jnp.matmul(cast_compute(h), emb.T,
                         preferred_element_type=jnp.float32)
        return out.astype(jnp.float32)


class LMAdapter(_AdapterBase):
    """Causal LM (``Transformer(mode="lm")``): the prompt prefills the
    self-attention cache; generation continues from its last token."""

    def __init__(self, model, params, cap: int, layout=None,
                 weight_quant=None):
        if model.mode != "lm":
            raise ValueError("LMAdapter needs a Transformer(mode='lm')")
        super().__init__(model, params, layout=layout,
                         weight_quant=weight_quant)
        layer = model.decoder[0].attn
        self.num_heads = layer.num_heads
        self.head_dim = layer.head_dim
        self.num_layers = len(model.decoder)
        self.vocab = model.vocab_size
        self._pe = positional_encoding(cap + 1, model.hidden_size)
        self._scale = jnp.sqrt(float(model.hidden_size))

    def ctx_specs(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        return {}

    def prepare(self, tokens: np.ndarray):
        """LM: the prompt IS the decoder prompt; no cross context."""
        return np.asarray(tokens, np.int32).reshape(-1), {}

    def chunk_forward(self, params, tokens, positions, kbuf, vbuf, ctx,
                      self_attend=None, model=None):
        """One step of C tokens per row: embed at absolute positions,
        write each layer's K/V into the buffer, attend causally over
        the cache, return last-layer logits.  ``kbuf/vbuf``:
        (B, L, h, K, hd) f32.  ``self_attend(i, q, k_new, v_new)``
        overrides the buffer attention (the engine's paged flash
        path, which owns its own cache writes); ``kbuf/vbuf`` may then
        be None.  ``model`` substitutes a same-architecture twin for
        the layer walk (the speculative DRAFT — identical params,
        block-sparse FFNs); attention/layer-norm modules are stateless
        so only the FFN forwards differ."""
        B, C = tokens.shape
        cap = self._pe.shape[0] - 1
        q_pos = positions[:, None] + jnp.arange(C)[None, :]        # (B,C)
        x = (jnp.take(params["embedding"], tokens.astype(jnp.int32),
                      axis=0) * self._scale
             + self._pe[q_pos].astype(jnp.float32))
        if self_attend is None:
            K = kbuf.shape[3]
            valid = jnp.arange(K)[None, None, :] <= q_pos[:, :, None]
        k_news, v_news = [], []
        for i, layer in enumerate((model or self.model).decoder):
            lp = params[f"dec{i}"]
            h1, _ = layer.ln1.forward(lp["ln1"], EMPTY, x)
            sp = lp["attn"]
            q = self._split(_attn_project(sp, h1, "wq", "bq"))
            k_new = self._split(_attn_project(sp, h1, "wk", "bk"))
            v_new = self._split(_attn_project(sp, h1, "wv", "bv"))
            if self_attend is not None:
                a = self_attend(i, q, k_new, v_new)
            else:
                kb = _write_chunk(kbuf[:, i], positions, k_new, cap)
                vb = _write_chunk(vbuf[:, i], positions, v_new, cap)
                kbuf = kbuf.at[:, i].set(kb)
                vbuf = vbuf.at[:, i].set(vb)
                a = self._attend(q, kb, vb, valid)
            x = x + self._merge(a, x, sp)
            h2, _ = layer.ln2.forward(lp["ln2"], EMPTY, x)
            f, _ = layer.ffn.forward(lp["ffn"], EMPTY, h2)
            x = x + f
            k_news.append(k_new)
            v_news.append(v_new)
        return (self._logits(x), kbuf, vbuf,
                jnp.stack(k_news, 1), jnp.stack(v_news, 1))

    def build_draft(self, spec: "SpecConfig"):
        """Construct the weight-shared speculative DRAFT twin
        (docs/serving.md §Speculative decoding): the same LM
        architecture rebuilt with ``ffn_sparsity=spec.sparsity``, whose
        :class:`~bigdl_tpu.ops.block_sparse.BlockSparseLinear` FFNs
        consume the target's params verbatim ({"weight", "bias"} — the
        Linear layout) and whose block masks are derived from the
        SERVED weights by one magnitude-pruning event
        (``derive_draft_masks``).  ``sparsity=0.0`` returns a dense
        twin — bit-identical to the target, acceptance rate 1.0."""
        from bigdl_tpu.nn.attention import Transformer

        m = self.model
        ffn_size = int(m.decoder[0].ffn.l1.out_features)
        sparsity = float(spec.sparsity)
        draft = Transformer(
            m.vocab_size, m.hidden_size, self.num_heads,
            ffn_size=ffn_size, num_layers=self.num_layers, dropout=0.0,
            mode="lm", ffn_sparsity=sparsity,
            sparse_block=tuple(spec.sparse_block))
        if sparsity > 0.0:
            from bigdl_tpu.ops.block_sparse import (derive_draft_masks,
                                                    iter_sparse_modules)

            if spec.draft_impl not in ("auto", "kernel", "masked"):
                raise ValueError(f"SpecConfig.draft_impl "
                                 f"{spec.draft_impl!r}: auto | kernel "
                                 "| masked")
            if spec.draft_impl == "auto":
                from bigdl_tpu.ops.common import on_tpu

                use_kernel = on_tpu()
            else:
                use_kernel = spec.draft_impl == "kernel"
            for _, mod in iter_sparse_modules(draft):
                mod.use_kernel = use_kernel
            # mask derivation reads the DEQUANTIZED weights under
            # weight_quant="int8" — block magnitudes of the f32 view
            derive_draft_masks(draft, self.params, sparsity)
        return draft


class Seq2SeqAdapter(_AdapterBase):
    """Translation transformer: "prefill" is the ENCODER — it turns the
    source sequence into per-layer cross-attention K/V context; the
    decoder prompt is a single BOS and every decode step is query-
    length 1 over the paged self-attention cache plus the fixed cross
    context (masked to the true source length)."""

    def __init__(self, model, params, cap: int, bos_id: int,
                 src_buckets: Sequence[int] = (8, 16, 32, 64),
                 layout=None, weight_quant=None):
        if model.mode != "translation":
            raise ValueError("Seq2SeqAdapter needs a translation-mode "
                             "Transformer")
        super().__init__(model, params, layout=layout,
                         weight_quant=weight_quant)
        layer = model.decoder[0].self_attn
        self.num_heads = layer.num_heads
        self.head_dim = layer.head_dim
        self.num_layers = len(model.decoder)
        self.vocab = model.vocab_size
        self.bos_id = bos_id
        self.src_buckets = tuple(sorted(src_buckets))
        self.src_cap = self.src_buckets[-1]
        self._pe = positional_encoding(cap + 1, model.hidden_size)
        self._scale = jnp.sqrt(float(model.hidden_size))
        self._encode_cache: Dict[int, Any] = {}

    def ctx_specs(self):
        L, h, hd = self.num_layers, self.num_heads, self.head_dim
        return {
            "ck": ((L, h, self.src_cap, hd), jnp.float32),
            "cv": ((L, h, self.src_cap, hd), jnp.float32),
            "src_len": ((), jnp.int32),
        }

    def _encode_fn(self, bucket: int):
        fn = self._encode_cache.get(bucket)
        if fn is None:
            model = self.model

            def encode(src, src_len):
                params = self.params
                # key-padding mask keeps padded source positions out of
                # encoder attention, so a bucket-padded encode matches
                # the exact-length encode row-for-row
                mask = (jnp.arange(bucket) < src_len)[None, None, None, :]
                x = model._embed(params, src)
                for i, layer in enumerate(model.encoder):
                    x, _ = layer.forward(params[f"enc{i}"], EMPTY, x,
                                         mask=mask)
                cks, cvs = [], []
                pad = self.src_cap - bucket
                for i in range(len(model.decoder)):
                    cp = params[f"dec{i}"]["cross_attn"]
                    ck = self._split(_attn_project(cp, x, "wk", "bk"))
                    cv = self._split(_attn_project(cp, x, "wv", "bv"))
                    cks.append(jnp.pad(
                        ck, ((0, 0), (0, 0), (0, pad), (0, 0)))[0])
                    cvs.append(jnp.pad(
                        cv, ((0, 0), (0, 0), (0, pad), (0, 0)))[0])
                return jnp.stack(cks), jnp.stack(cvs)

            fn = self.jit(encode)
            self._encode_cache[bucket] = fn
        return fn

    def prepare(self, tokens: np.ndarray):
        src = np.asarray(tokens, np.int32).reshape(1, -1)
        t = src.shape[1]
        bucket = next((b for b in self.src_buckets if b >= t), None)
        if bucket is None:
            raise ValueError(f"source length {t} exceeds the largest "
                             f"src bucket {self.src_buckets[-1]}")
        if bucket > t:
            src = np.pad(src, ((0, 0), (0, bucket - t)))
        ck, cv = self._encode_fn(bucket)(src, np.int32(t))
        ctx = {"ck": ck, "cv": cv, "src_len": np.int32(t)}
        return np.asarray([self.bos_id], np.int32), ctx

    def warmup_buckets(self, sample_src_lens: Optional[Sequence[int]] = None):
        for b in (sample_src_lens or self.src_buckets):
            b = int(b)
            jax.block_until_ready(self._encode_fn(b)(
                np.zeros((1, b), np.int32), np.int32(b)))

    def chunk_forward(self, params, tokens, positions, kbuf, vbuf, ctx,
                      self_attend=None):
        """Decoder step: causal self-attention over the cache plus
        cross-attention over the per-row encoder context — mirrors
        ``transformer_decode_cached`` op-for-op so the engine path
        stays byte-compatible with the legacy one-scan service."""
        B, C = tokens.shape
        cap = self._pe.shape[0] - 1
        q_pos = positions[:, None] + jnp.arange(C)[None, :]
        x = (jnp.take(params["embedding"], tokens.astype(jnp.int32),
                      axis=0) * self._scale
             + self._pe[q_pos].astype(jnp.float32))
        if self_attend is None:
            K = kbuf.shape[3]
            valid = jnp.arange(K)[None, None, :] <= q_pos[:, :, None]
        src_valid = (jnp.arange(self.src_cap)[None, None, :]
                     < ctx["src_len"].reshape(-1, 1, 1))       # (B,1,Tcap)
        src_valid = jnp.broadcast_to(src_valid, (B, C, self.src_cap))
        k_news, v_news = [], []
        for i, layer in enumerate(self.model.decoder):
            lp = params[f"dec{i}"]
            h1, _ = layer.ln1.forward(lp["ln1"], EMPTY, x)
            sp = lp["self_attn"]
            q = self._split(_attn_project(sp, h1, "wq", "bq"))
            k_new = self._split(_attn_project(sp, h1, "wk", "bk"))
            v_new = self._split(_attn_project(sp, h1, "wv", "bv"))
            if self_attend is not None:
                a = self_attend(i, q, k_new, v_new)
            else:
                kb = _write_chunk(kbuf[:, i], positions, k_new, cap)
                vb = _write_chunk(vbuf[:, i], positions, v_new, cap)
                kbuf = kbuf.at[:, i].set(kb)
                vbuf = vbuf.at[:, i].set(vb)
                a = self._attend(q, kb, vb, valid)
            x = x + self._merge(a, x, sp)
            h2, _ = layer.ln2.forward(lp["ln2"], EMPTY, x)
            cp = lp["cross_attn"]
            qc = self._split(_attn_project(cp, h2, "wq", "bq"))
            a = self._attend(qc, ctx["ck"][:, i], ctx["cv"][:, i],
                             src_valid)
            x = x + self._merge(a, x, cp)
            h3, _ = layer.ln3.forward(lp["ln3"], EMPTY, x)
            f, _ = layer.ffn.forward(lp["ffn"], EMPTY, h3)
            x = x + f
            k_news.append(k_new)
            v_news.append(v_new)
        return (self._logits(x), kbuf, vbuf,
                jnp.stack(k_news, 1), jnp.stack(v_news, 1))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class DecodeEngine:
    """Fixed slot pool + paged KV cache + step-granular scheduling.

    Thread model: clients call :meth:`submit` (any thread); one engine
    thread owns the slots, pages, and device cache buffers.  Results
    are delivered through ``DecodeRequest.wait()`` / ``on_done``;
    per-token streaming through ``on_token`` (called on the engine
    thread — keep callbacks cheap)."""

    def __init__(self, adapter, config: Optional[DecodeConfig] = None,
                 metrics=None, name: str = "decode"):
        self.adapter = adapter
        self.cfg = config or DecodeConfig()
        if metrics is None:
            from bigdl_tpu.optim.metrics import global_metrics

            metrics = global_metrics()
        self.metrics = metrics
        self.name = name
        cfg = self.cfg
        L, h, hd = adapter.num_layers, adapter.num_heads, adapter.head_dim
        if cfg.slots < 2 or cfg.prefill_batch < 2:
            raise ValueError("DecodeConfig.slots and prefill_batch must "
                             "be >= 2 (single-row programs take a "
                             "different XLA reduction path and break "
                             "decode parity)")
        if cfg.kv_dtype not in ("float32", "int8"):
            raise ValueError(f"DecodeConfig.kv_dtype must be 'float32' "
                             f"or 'int8', got {cfg.kv_dtype!r}")
        # int8 pages (docs/quantization.md §Serving memory hierarchy):
        # pages store int8 payloads; one f32 abs-max scale per (layer,
        # page) rides alongside.  The scale tables exist for the f32
        # engine too (L*P floats — noise next to the pool) so every
        # jitted program has ONE signature; the f32 trace just passes
        # them through untouched.
        self._quant_kv = cfg.kv_dtype == "int8"
        kv_dt = jnp.int8 if self._quant_kv else jnp.float32
        self._kv_k = jnp.zeros((L, cfg.total_pages, h, cfg.page_size, hd),
                               kv_dt)
        self._kv_v = jnp.zeros_like(self._kv_k)
        self._kv_sk = jnp.zeros((L, cfg.total_pages), jnp.float32)
        self._kv_sv = jnp.zeros_like(self._kv_sk)
        # pages popped from the free list whose scales still carry the
        # previous owner's value — zeroed (in fixed-width chunks) before
        # the next program dispatch so a reclaimed page can never
        # dequantize stale payload against a stale scale
        self._fresh_pages: List[int] = []
        self._ctx_bufs = {
            k: jnp.zeros((cfg.slots,) + shape, dtype)
            for k, (shape, dtype) in adapter.ctx_specs().items()}
        # host-side slot boards (numpy; converted per dispatch)
        S = cfg.slots
        self._page_table = np.zeros((S, cfg.pages_per_slot), np.int32)
        self._lengths = np.zeros((S,), np.int32)
        self._last_tokens = np.zeros((S,), np.int32)
        self._active_mask = np.zeros((S,), bool)
        # per-slot request SEEDS — the request key fold happens inside
        # the compiled programs (an eager fold_in per admission costs a
        # device round-trip on the hot loop)
        self._seeds = np.zeros((S,), np.int32)
        self._temps = np.zeros((S,), np.float32)
        self._top_ks = np.zeros((S,), np.int32)
        self._top_ps = np.ones((S,), np.float32)
        self._slots: List[Optional[_ActiveSeq]] = [None] * S
        self._free_pages: List[int] = list(range(cfg.total_pages))
        self._reserved_pages = 0
        # prefix/KV reuse (docs/serving.md §Decode fleet): pages held by
        # the cache leave _free_pages — page accounting stays exact
        self._prefix_cache = None
        if cfg.prefix_cache_pages > 0 and cfg.continuous:
            from bigdl_tpu.serving.fleet.prefix_cache import PrefixCache

            self._prefix_cache = PrefixCache(
                min(cfg.prefix_cache_pages, cfg.total_pages),
                cfg.page_size, page_dtype=cfg.kv_dtype)
        # speculative decoding (docs/serving.md §Speculative decoding):
        # the draft's KV pages live in a parallel ALWAYS-f32 pool
        # indexed by the SAME page table — one allocation/release path,
        # so a cancelled or expired slot structurally cannot leak draft
        # pages (tests/test_spec_decode.py pins the regression)
        self._spec = cfg.speculative
        self._draft_model = None
        self._dr_k = self._dr_v = None
        if self._spec is not None:
            sp = self._spec
            if not cfg.continuous:
                raise ValueError("speculative decoding requires "
                                 "continuous mode")
            if adapter.ctx_specs() or not hasattr(adapter,
                                                  "build_draft"):
                raise ValueError(
                    "speculative decoding supports LM adapters only "
                    "(a seq2seq draft would need its own cross "
                    "context)")
            if not 1 <= int(sp.k) < cfg.cap:
                raise ValueError(f"SpecConfig.k must be in [1, "
                                 f"{cfg.cap}), got {sp.k}")
            if sp.verify_impl not in ("auto", "scan", "chunk"):
                raise ValueError(
                    f"SpecConfig.verify_impl {sp.verify_impl!r}: "
                    "auto | scan | chunk")
            if sp.verify_impl == "chunk" and cfg.kv_dtype != "float32":
                raise ValueError(
                    "SpecConfig.verify_impl='chunk' requires f32 KV "
                    "pages (int8 page RMW is per-position; the scan "
                    "verify handles kv_dtype='int8')")
            if sp.draft_window is not None and int(sp.draft_window) < 1:
                raise ValueError(
                    f"SpecConfig.draft_window must be None or >= 1, "
                    f"got {sp.draft_window}")
            self._draft_model = adapter.build_draft(sp)
            self._dr_k = jnp.zeros(
                (L, cfg.total_pages, h, cfg.page_size, hd), jnp.float32)
            self._dr_v = jnp.zeros_like(self._dr_k)
        self._draft_fns: Dict[int, Callable] = {}
        self._verify_fns: Dict[int, Callable] = {}
        self._draft_prefill_fns: Dict[int, Callable] = {}
        self._accept_window = deque(maxlen=256)  # (t, accepted, adjudicated)
        self._import_fn: Optional[Callable] = None
        self._scale_reset_fn: Optional[Callable] = None
        self._base_key = jax.random.PRNGKey(cfg.base_seed)
        # work queue: (deadline_t, seq, req) — the PR 8 deadline-heap
        # ordering at decode-queue granularity
        self._heap: List[Tuple[float, int, DecodeRequest]] = []
        self._seq = itertools.count(1)
        self._wave_steps = 0     # continuous=False: steps into the wave
        self._wave_horizon = cfg.max_new_tokens
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # jitted program caches — keyed by bucket pages (closed set)
        self._step_fns: Dict[int, Callable] = {}
        self._prefill_fns: Dict[int, Callable] = {}
        self._prefill_scratch: Optional[Dict[str, np.ndarray]] = None
        self._gauge_t = 0.0
        self._last_step_t = 0.0
        self._ctx_write_fn: Optional[Callable] = None
        self._static_prefill_fns: Dict[Tuple[int, int], Callable] = {}
        self._static_scan_fns: Dict[Tuple[int, int], Callable] = {}
        # event ring for scheduling specs ("prefill_chunk"/"decode_step")
        # — also dumped by the flight recorder next to metrics_snapshot
        # (weakref'd: a collected engine's ring is pruned, not pinned)
        self.events: deque = deque(maxlen=512)
        flight.register_dump_source(
            f"decode_engine:{name}:{id(self):x}", self._ring_snapshot)
        self._tokens_window = deque(maxlen=256)   # (t, n) for tokens/s
        # cross-thread cancellation: rid -> reason, swept on the engine
        # thread; _iter_lock serializes one engine iteration against
        # migrate_live_slots so an export+freeze is atomic w.r.t. steps
        self._cancelled: Dict[str, str] = {}
        self._iter_lock = threading.Lock()
        self.stats = {"requests": 0, "completed": 0, "expired": 0,
                      "tokens": 0, "steps": 0, "prefill_chunks": 0,
                      "rejected": 0, "kv_exports": 0, "kv_imports": 0,
                      "cancelled": 0, "spec_drafted": 0,
                      "spec_accepted": 0, "spec_rejected": 0}
        self.metrics.describe(
            "serving.decode.tokens_per_s",
            "generated tokens/s over the recent step window")

    # -- client side --------------------------------------------------------
    def submit(self, req: DecodeRequest) -> DecodeRequest:
        if self._stop.is_set():
            raise RuntimeError("decode engine stopped")
        prompt_preview = np.asarray(req.tokens, np.int32).reshape(-1)
        if len(prompt_preview) == 0:
            # an empty prompt would occupy a slot that can never
            # prefill, decode, or expire — reject at the door
            raise ValueError("empty prompt: a generate request needs at "
                             "least one input token")
        if getattr(self.adapter, "bos_id", None) is None \
                and len(prompt_preview) >= self.cfg.cap:
            raise ValueError(
                f"prompt of {len(prompt_preview)} tokens exceeds the "
                f"cache cap {self.cfg.cap} (page_size * pages_per_slot)")
        if req.handoff is not None or req.export_kv:
            self._validate_fleet_request(req, prompt_preview)
        req.admit_t = time.time()
        req.rid = req.rid or f"{self.name}-{next(self._seq)}"
        with self._cv:
            if len(self._heap) >= self.cfg.queue_capacity:
                self.stats["rejected"] += 1
                raise RuntimeError("decode queue full")
            req.seq = next(self._seq)
            heapq.heappush(self._heap, (req.deadline_t, req.seq, req))
            self._cv.notify_all()
        self._ensure_thread()
        return req

    def generate(self, prompts, **kw) -> List[DecodeResult]:
        """Synchronous helper: submit every prompt, wait for all."""
        reqs = [self.submit(DecodeRequest(tokens=np.asarray(p), **kw))
                for p in prompts]
        return [r.wait(timeout=120.0) for r in reqs]

    def submit_prefilled(self, handoff: dict, **kw) -> DecodeRequest:
        """Admit a request whose chunked prefill ran on ANOTHER worker
        (docs/serving.md §Decode fleet): ``handoff`` is the dict
        ``fleet.handoff.unpack_handoff`` returns — prompt tokens, the
        first generated token + its log-prob, and the exact float32
        page images of the prompt's KV.  Sampling params/seed default
        to the handoff's own (they MUST match the prefill's for the
        parity invariant to mean anything); ``kw`` overrides ride
        through to :class:`DecodeRequest` (max_new_tokens, rid,
        on_token, deadline_t...)."""
        meta = {k: handoff[k]
                for k in ("temperature", "top_k", "top_p", "seed")
                if k in handoff}
        meta.update(kw)
        req = DecodeRequest(
            tokens=np.asarray(handoff["tokens"], np.int32),
            handoff=handoff, **meta)
        return self.submit(req)

    def _validate_fleet_request(self, req: DecodeRequest,
                                prompt: np.ndarray) -> None:
        """Reject a malformed handoff/export at the door — once
        admitted it would fail on the engine thread and take the whole
        in-flight batch down with it."""
        if not self.cfg.continuous:
            raise ValueError("KV handoff/export requires continuous mode")
        if self.adapter.ctx_specs():
            raise ValueError(
                "KV handoff/export supports LM adapters only (a seq2seq "
                "'prefill' is the encoder — there are no prompt KV "
                "pages to transfer)")
        if req.handoff is None:
            return
        h = req.handoff
        cfg, a = self.cfg, self.adapter
        hd_dt = str(h.get("kv_dtype", "float32"))
        if hd_dt != cfg.kv_dtype:
            # mixed-dtype pages must never be imported (an f32 engine
            # has no scale tables; an int8 engine would quantize-import
            # an f32 image and silently break handoff parity) — the
            # pool proxy degrades this slot to re-prefill failover
            raise ValueError(
                f"handoff kv_dtype {hd_dt!r} does not match this "
                f"engine's kv_dtype {cfg.kv_dtype!r}; refusing the "
                "page import (re-prefill instead)")
        n = -(-len(prompt) // cfg.page_size)
        want = (a.num_layers, n, a.num_heads, cfg.page_size, a.head_dim)
        k = np.asarray(h.get("k"))
        v = np.asarray(h.get("v"))
        if k.shape != want or v.shape != want:
            raise ValueError(f"handoff K/V shape {k.shape} does not "
                             f"match engine geometry {want}")
        if self._quant_kv:
            ks = np.asarray(h.get("k_scales"))
            vs = np.asarray(h.get("v_scales"))
            if ks.shape != (a.num_layers, n) \
                    or vs.shape != (a.num_layers, n):
                raise ValueError(
                    f"int8 handoff scale shape {ks.shape} does not "
                    f"match (layers, pages) {(a.num_layers, n)}")
        toks = np.asarray(h.get("tokens"), np.int32).reshape(-1)
        if not np.array_equal(toks, prompt):
            raise ValueError("handoff prompt tokens do not match the "
                             "request's tokens")

    def _ring_snapshot(self) -> dict:
        """The scheduling ring (slot admissions, expiries, prefill
        interleave) as one flight-dump line — a decode postmortem needs
        WHAT the scheduler did, not just the counters."""
        return {"engine": self.name,
                "events": [list(e) for e in list(self.events)],
                "stats": dict(self.stats)}

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._heap)

    def active_slots(self) -> int:
        return int(self._active_mask.sum())

    def kv_bytes_per_page(self) -> int:
        """HBM bytes one page row costs across every layer's K AND V
        pool, in the ACTUAL stored dtype — plus, for int8, the two f32
        scales per (layer, page).  This is the figure the wire/HBM
        ledger and the router's capacity scoring price pages by."""
        a = self.adapter
        elems = (a.num_layers * a.num_heads * self.cfg.page_size
                 * a.head_dim)
        itemsize = 1 if self._quant_kv else 4
        scale_bytes = 2 * a.num_layers * 4 if self._quant_kv else 0
        # speculation: every page id also has a row in the f32 draft
        # K/V pool — the fleet router must price that honestly
        draft_bytes = 2 * elems * 4 if self._spec is not None else 0
        return 2 * elems * itemsize + scale_bytes + draft_bytes

    def decode_pressure(self) -> Dict[str, Any]:
        """Admission-pressure snapshot for the fleet router
        (docs/serving.md §Decode fleet): free slots, reservable pages,
        and the prefill backlog (prefilling slots + queued requests).
        Read from any thread — a torn read across fields only skews a
        heuristic score, never correctness."""
        queued = self.queue_depth()
        slots = list(self._slots)
        out = {
            "total_slots": self.cfg.slots,
            "free_slots": sum(s is None for s in slots),
            "total_pages": self.cfg.total_pages,
            "free_pages": max(
                len(self._free_pages) - self._reserved_pages, 0),
            "queued": queued,
            "prefill_backlog": queued + sum(
                1 for s in slots if s is not None and s.prefilling),
            "active": int(self._active_mask.sum()),
            # proof the physical split is live, not just configured
            "kv_exports": self.stats["kv_exports"],
            "kv_imports": self.stats["kv_imports"],
            # page capacity in BYTES, not just counts: the fleet router
            # must not score an int8 worker's free page and an f32
            # worker's free page as equal capacity (docs/serving.md
            # §Decode fleet)
            "page_dtype": self.cfg.kv_dtype,
            "kv_bytes_per_page": self.kv_bytes_per_page(),
            # draft-page accounting is structural (same page ids), so
            # free_pages above is already honest under speculation —
            # these keys just let the router see the mode and the
            # per-iteration page burst (+k positions per active slot)
            "speculative": self._spec is not None,
            "spec_k": int(self._spec.k) if self._spec is not None else 0,
        }
        if self._prefix_cache is not None:
            out["prefix_cache"] = self._prefix_cache.stats()
        return out

    # -- cancellation / live migration (docs/serving.md §Fleet fault
    # tolerance) ------------------------------------------------------------
    def cancel(self, rid: str, reason: str = "cancelled") -> None:
        """Cancel a queued or in-flight request from any thread.  The
        engine thread sweeps the mark at the next iteration: a queued
        request is dropped from the heap, an active slot frees its
        pages immediately (a disconnected stream must not decode to
        ``max_new_tokens`` on a dead socket).  Unknown rids are a no-op
        — the request may have just finished."""
        with self._cv:
            self._cancelled[rid] = reason
            self._cv.notify_all()

    def _sweep_cancelled(self) -> None:
        with self._cv:
            if not self._cancelled:
                return
            marks = self._cancelled
            self._cancelled = {}
            keep = [(d, q, r) for d, q, r in self._heap
                    if r.rid not in marks]
            dropped = [r for _, _, r in self._heap if r.rid in marks]
            if dropped:
                self._heap = keep
                heapq.heapify(self._heap)
        for req in dropped:
            self.events.append(("cancel_queued", req.rid,
                                marks[req.rid]))
            self._count_cancel(marks[req.rid])
            self._finish_error(
                req, RequestCancelledError(req.rid, marks[req.rid]))
        for s, seq in enumerate(self._slots):
            if seq is not None and seq.req.rid in marks:
                reason = marks[seq.req.rid]
                self.events.append(("cancel", seq.req.rid, s, reason))
                self._count_cancel(reason)
                err = RequestCancelledError(seq.req.rid, reason)
                if seq.generated:
                    err.partial_tokens = np.asarray(
                        seq.generated, np.int32)
                self._finish_error(seq.req, err)
                self._release_slot(s)

    def _count_cancel(self, reason: str) -> None:
        self.stats["cancelled"] += 1
        self.metrics.inc("serving.decode.cancelled")
        if reason == "client_disconnect":
            self.metrics.inc("serving.decode.client_disconnects")

    def migrate_live_slots(self) -> Tuple[List[dict], List[str], List[str]]:
        """Freeze-and-export every migratable live slot (docs/serving.md
        §Fleet fault tolerance): under ``_iter_lock`` — atomically
        w.r.t. engine iterations, so no token is emitted after its
        slot's state left — copy each eligible slot's written KV pages
        plus sampling state into a handoff dict the peer can import via
        ``submit_prefilled``, and deactivate the slot.  The caller
        ships the blobs, THEN evicts the frozen rids with
        :meth:`cancel` (``reason="migrated"``), so the peer has parked
        the state before the victim's stream aborts.

        The export is shaped exactly as a fresh prefill of
        ``prompt + generated[:-1]`` would export: ``lengths[s]`` cache
        positions are written (the pending last token's K/V lands next
        step, so it travels as ``first_token``), and the byte-parity
        invariant (counter-based sampling keys at absolute positions)
        makes the importing engine's continuation byte-identical to the
        no-fault run.

        Returns ``(exports, frozen_rids, leftover_rids)`` — leftover =
        live-but-ineligible (still prefilling, no token yet, or
        seq2seq) plus queued generate requests; the caller evicts those
        too and lets the proxy's re-prefill failover recover them."""
        exports: List[dict] = []
        frozen: List[str] = []
        leftover: List[str] = []
        cfg = self.cfg
        if not cfg.continuous:
            return exports, frozen, leftover
        with self._iter_lock:
            for s, seq in enumerate(self._slots):
                if seq is None or seq.done or seq.frozen:
                    continue
                req = seq.req
                eligible = (not seq.ctx and not seq.prefilling
                            and len(seq.generated) >= 1
                            and not req.export_kv)
                if not eligible:
                    leftover.append(req.rid)
                    continue
                n = -(-int(self._lengths[s]) // cfg.page_size)
                pids = np.zeros((cfg.pages_per_slot,), np.int32)
                pids[:n] = self._page_table[s, :n]
                k = np.asarray(self._kv_k[:, pids])[:, :n]
                v = np.asarray(self._kv_v[:, pids])[:, :n]
                tokens = np.concatenate([
                    np.asarray(seq.prompt, np.int32),
                    np.asarray(seq.generated[:-1], np.int32)])
                export = {
                    "tokens": tokens,
                    "first_token": int(seq.generated[-1]),
                    "first_logp": float(seq.last_logp),
                    "temperature": float(req.temperature),
                    "top_k": int(req.top_k),
                    "top_p": float(req.top_p),
                    "seed": int(req.seed),
                    "request_id": req.rid,
                    "migrated": True,
                    "resume_len": len(seq.generated),
                    "kv_dtype": cfg.kv_dtype,
                    "k": k,
                    "v": v,
                }
                if self._quant_kv:
                    export["k_scales"] = np.asarray(
                        self._kv_sk[:, pids], np.float32)[:, :n]
                    export["v_scales"] = np.asarray(
                        self._kv_sv[:, pids], np.float32)[:, :n]
                exports.append(export)
                seq.frozen = True
                self._active_mask[s] = False
                frozen.append(req.rid)
                self.stats["kv_exports"] += 1
                self.metrics.inc("serving.fleet.kv_exports")
                self.events.append(("kv_export", req.rid, int(n)))
            with self._cv:
                leftover.extend(r.rid for _, _, r in self._heap)
        return exports, frozen, leftover

    # -- lifecycle ----------------------------------------------------------
    def _ensure_thread(self) -> None:
        # under the cv lock: concurrent submits must never race TWO
        # engine threads into existence — both would donate the same
        # device cache buffers and poison every later dispatch
        with self._cv:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, daemon=True,
                    name=f"decode-{self.name}")
                self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
        # fail whatever is still queued or in flight — explicit verdicts
        with self._cv:
            queued = [r for _, _, r in self._heap]
            self._heap.clear()
        for req in queued:
            self._finish_error(req, RuntimeError(
                f"decode request {req.rid} dropped: engine stopped"))
        if self._thread is not None and self._thread.is_alive():
            # the engine thread is wedged past the join budget: touching
            # slot/page state from here would race its own release path
            # (a double page free = cross-request KV aliasing).  Leak
            # the in-flight requests instead — strictly safer.
            log.error("decode engine thread did not exit within 10s; "
                      "leaving in-flight slots to it")
            return
        for s, seq in enumerate(self._slots):
            if seq is not None:
                if not seq.done:   # a done (gang-mode) seat already
                    #                delivered its result
                    self._finish_error(seq.req, RuntimeError(
                        f"decode request {seq.req.rid} dropped: engine "
                        "stopped"))
                self._release_slot(s)

    def warmup(self) -> "DecodeEngine":
        """Compile the CLOSED program set before traffic: one decode
        step and one prefill program per cache-length bucket (plus the
        adapter's encode buckets), inside ``expected_compile`` so the
        recompile sentinel stays quiet.  After this, a mixed prompt/
        generation-length sweep runs with zero XLA compiles."""
        from bigdl_tpu.obs.attr import expected_compile

        with expected_compile():
            if hasattr(self.adapter, "warmup_buckets"):
                self.adapter.warmup_buckets()
            # the one eager jax op on the admission path: the
            # per-request key fold.  Same shapes for every seed, so one
            # call here keeps the first real admission compile-free
            np.asarray(jax.random.fold_in(self._base_key, 0))
            for nb in self.cfg.len_buckets():
                self._step_fn(nb)
                self._prefill_fn(nb)
                if self._spec is not None:
                    # the draft/verify/draft-prefill programs join the
                    # SAME closed bucket set — a spec-on mixed sweep
                    # stays at zero unexpected recompiles
                    self._draft_fn(nb)
                    self._verify_fn(nb)
                    self._verify_fn(nb, force_scan=True)
                    self._draft_prefill_fn(nb)
            if self._ctx_bufs:
                # CALL the ctx-write program (jit() alone compiles
                # nothing): the first seq2seq admission must not pay —
                # or flag — a mid-traffic compile
                zeros = {k: jnp.zeros_like(v[0])
                         for k, v in self._ctx_bufs.items()}
                self._ctx_bufs = self._ctx_write()(self._ctx_bufs, 0,
                                                   zeros)
            # trace each program once on zero inputs (compile happens at
            # first CALL, not jit(); results discarded, buffers donated
            # copies so live state is untouched)
            self._warm_run()
            if not self.adapter.ctx_specs():
                # the fleet handoff-import scatter (LM only): one fixed
                # shape — all-dropped page ids make the warm call a
                # no-op on the live cache
                cfg = self.cfg
                a = self.adapter
                z = np.zeros((a.num_layers, cfg.pages_per_slot,
                              a.num_heads, cfg.page_size, a.head_dim),
                             np.int8 if self._quant_kv else np.float32)
                zs = np.zeros((a.num_layers, cfg.pages_per_slot),
                              np.float32)
                (self._kv_k, self._kv_v, self._kv_sk,
                 self._kv_sv) = self._import_write()(
                    self._kv_k, self._kv_v, self._kv_sk, self._kv_sv,
                    np.full((cfg.pages_per_slot,), cfg.total_pages,
                            np.int32), z, z, zs, zs)
                # ...and the export gather (same fixed index width)
                np.asarray(self._kv_k[
                    :, np.zeros((cfg.pages_per_slot,), np.int32)])
                jax.block_until_ready(self._kv_k)
        return self

    def _warm_run(self) -> None:
        cfg = self.cfg
        S = cfg.slots
        kv_k, kv_v = self._kv_k, self._kv_v
        kv_sk, kv_sv = self._kv_sk, self._kv_sv
        dr_k, dr_v = self._dr_k, self._dr_v
        for nb in cfg.len_buckets():
            kv_k, kv_v, kv_sk, kv_sv, _, _ = self._step_fn(nb)(
                kv_k, kv_v, kv_sk, kv_sv, self._ctx_bufs,
                self._page_table, np.zeros((S,), np.int32),
                np.zeros((S,), np.int32),
                np.zeros((S,), bool), np.zeros((S,), np.int32),
                np.zeros((S,), np.float32), np.zeros((S,), np.int32),
                np.ones((S,), np.float32))
            B = cfg.prefill_batch
            kv_k, kv_v, kv_sk, kv_sv, _, _ = self._prefill_fn(nb)(
                kv_k, kv_v, kv_sk, kv_sv, self._ctx_bufs,
                np.zeros((B,), np.int32),
                np.zeros((B, cfg.pages_per_slot), np.int32),
                np.zeros((B, cfg.prompt_chunk), np.int32),
                np.zeros((B,), np.int32), np.zeros((B,), np.int32),
                np.zeros((B,), bool), np.zeros((B,), np.int32),
                np.zeros((B,), np.float32), np.zeros((B,), np.int32),
                np.ones((B,), np.float32))
            if self._spec is not None:
                # all-inactive rows: every write masks out, so the warm
                # calls compile without touching live pool state
                dr_k, dr_v, _ = self._draft_fn(nb)(
                    dr_k, dr_v, self._page_table,
                    np.zeros((S,), np.int32), np.zeros((S,), np.int32),
                    np.zeros((S,), bool), np.zeros((S,), np.int32),
                    np.zeros((S,), np.float32),
                    np.zeros((S,), np.int32), np.ones((S,), np.float32))
                for force_scan in (False, True):
                    kv_k, kv_v, kv_sk, kv_sv, _, _ = self._verify_fn(
                        nb, force_scan)(
                        kv_k, kv_v, kv_sk, kv_sv, self._page_table,
                        np.zeros((S,), np.int32),
                        np.zeros((S, self._spec.k + 1), np.int32),
                        np.zeros((S,), np.int32), np.zeros((S,), bool),
                        np.zeros((S,), np.int32),
                        np.zeros((S,), np.float32),
                        np.zeros((S,), np.int32),
                        np.ones((S,), np.float32))
                dr_k, dr_v = self._draft_prefill_fn(nb)(
                    dr_k, dr_v,
                    np.zeros((B, cfg.pages_per_slot), np.int32),
                    np.zeros((B, cfg.prompt_chunk), np.int32),
                    np.zeros((B,), np.int32), np.zeros((B,), bool))
        if self._quant_kv:
            # the scale-reset program (all page ids dropped — no-op on
            # the live tables)
            kv_sk, kv_sv = self._scale_reset()(
                kv_sk, kv_sv,
                np.full((cfg.pages_per_slot,), cfg.total_pages,
                        np.int32))
            # ...and the fixed-width scale gather the harvest/migration
            # exports run
            np.asarray(kv_sk[:, np.zeros((cfg.pages_per_slot,),
                                         np.int32)])
        jax.block_until_ready(kv_k)
        self._kv_k, self._kv_v = kv_k, kv_v
        self._kv_sk, self._kv_sv = kv_sk, kv_sv
        if self._spec is not None:
            jax.block_until_ready(dr_k)
            self._dr_k, self._dr_v = dr_k, dr_v

    # -- jitted programs ----------------------------------------------------
    def _gather(self, kv, pt):
        """(L, P, h, page, hd)[pages pt (B, nb)] -> (B, L, h, nb*page,
        hd) contiguous per-slot cache view."""
        g = kv[:, pt]                       # (L, B, nb, h, page, hd)
        L, B, nb, h, page, hd = g.shape
        return g.transpose(1, 0, 3, 2, 4, 5).reshape(B, L, h, nb * page,
                                                     hd)

    def _write_chunk_pages(self, pool, new, page_table, lengths,
                           active):
        """Persist a speculative chunk's K/V into an f32 page pool with
        ONE page-granular scatter.  ``new`` is (L, B, h, C, hd) — fresh
        K or V for positions ``lengths..lengths+C-1`` per slot.  A
        cell-granular ``.at[:, pid, :, off]`` scatter costs B*C scatter
        rows (XLA CPU serializes them — it dominated the whole verify
        call); the chunk only ever touches ``ceil(C/page)+1``
        consecutive pages per slot, so gather those, splice the chunk
        in with a vectorized ``where``, and write whole pages back."""
        cfg = self.cfg
        page = cfg.page_size
        L, B, h, C, hd = new.shape
        TP = (C - 1) // page + 2          # straddle: one extra page
        p0 = lengths // page
        tp = p0[:, None] + jnp.arange(TP)[None, :]           # (B, TP)
        pid = jnp.take_along_axis(
            page_table, jnp.clip(tp, 0, cfg.pages_per_slot - 1),
            axis=1)
        # out-of-range slots/pages go to the dump index and drop — and
        # never duplicate an in-range pid, keeping the scatter
        # conflict-free (duplicate rows would race)
        pid = jnp.where(active[:, None] & (tp < cfg.pages_per_slot),
                        pid, cfg.total_pages)
        cell = (tp[:, :, None] * page
                + jnp.arange(page)[None, None, :])       # (B, TP, page)
        c = cell - lengths[:, None, None]   # chunk index of each cell
        inside = (c >= 0) & (c < C) & (cell < cfg.cap)
        cc = jnp.clip(c, 0, C - 1).reshape(1, B, 1, TP * page)
        sel = jnp.take_along_axis(
            new, jnp.broadcast_to(cc[..., None], (L, B, h, TP * page,
                                                  hd)), axis=3)
        sel = sel.reshape(L, B, h, TP, page, hd).transpose(
            0, 1, 3, 2, 4, 5)                # (L, B, TP, h, page, hd)
        old = pool[:, pid]
        mask = inside[None, :, :, None, :, None]
        return pool.at[:, pid].set(jnp.where(mask, sel, old),
                                   mode="drop")

    def _gather_deq(self, kv, sc, pt):
        """:meth:`_gather` for int8 pools: dequantize each gathered page
        against its (layer, page) scale before flattening — a freshly
        allocated page carries scale 0.0, so its stale int8 payload
        dequantizes to exact zeros."""
        g = (kv[:, pt].astype(jnp.float32)
             * sc[:, pt][..., None, None, None])
        L, B, nb, h, page, hd = g.shape
        return g.transpose(1, 0, 3, 2, 4, 5).reshape(B, L, h, nb * page,
                                                     hd)

    def _scale_reset(self):
        if self._scale_reset_fn is None:
            def reset(sk, sv, pids):
                return (sk.at[:, pids].set(0.0, mode="drop"),
                        sv.at[:, pids].set(0.0, mode="drop"))

            self._scale_reset_fn = jax.jit(reset, donate_argnums=(0, 1))
        return self._scale_reset_fn

    def _flush_fresh_scales(self) -> None:
        """Zero the scales of pages just popped off the free list (int8
        only), BEFORE the next program dispatch: a reclaimed page
        otherwise inherits its previous owner's scale and dequantizes
        that owner's stale payload — the stale-scale aliasing hazard
        tests/test_quant_serving.py pins.  Fixed ``pages_per_slot``-wide
        chunks (out-of-range padding drops) keep the compile set
        closed."""
        if not self._fresh_pages:
            return
        fresh, self._fresh_pages = self._fresh_pages, []
        if not self._quant_kv:
            return
        W = self.cfg.pages_per_slot
        fn = self._scale_reset()
        for c0 in range(0, len(fresh), W):
            pids = np.full((W,), self.cfg.total_pages, np.int32)
            chunk = fresh[c0:c0 + W]
            pids[:len(chunk)] = chunk
            self._kv_sk, self._kv_sv = fn(self._kv_sk, self._kv_sv,
                                          pids)

    def _use_flash(self) -> bool:
        if self.cfg.use_flash_decode is not None:
            return bool(self.cfg.use_flash_decode)
        from bigdl_tpu.ops.common import on_tpu

        return on_tpu()

    def _step_fn(self, n_blocks: int):
        fn = self._step_fns.get(n_blocks)
        if fn is not None:
            return fn
        cfg = self.cfg
        adapter = self.adapter
        page = cfg.page_size
        use_flash = self._use_flash()
        quant = self._quant_kv

        base_key = jnp.asarray(np.asarray(self._base_key))

        def step(kv_k, kv_v, kv_sk, kv_sv, ctx_bufs, page_table, lengths,
                 last_tokens, active, seeds, temps, top_ks, top_ps):
            keys = jax.vmap(jax.random.fold_in)(
                jnp.broadcast_to(base_key, (seeds.shape[0], 2)), seeds)
            pt = page_table[:, :n_blocks]
            # write target of this step's K/V: the page holding position
            # ``lengths`` (inactive slots get an out-of-range page id ->
            # the scatter drops their write)
            wid = jnp.where(active,
                            jnp.take_along_axis(
                                page_table, (lengths // page)[:, None],
                                axis=1)[:, 0],
                            cfg.total_pages)
            off = lengths % page
            if quant:
                # int8 pages (docs/quantization.md §Serving memory
                # hierarchy): read-modify-write ONLY the page holding
                # this step's position — dequantize it, insert the new
                # row, requantize under a monotone per-page scale (an
                # unchanged page round-trips exactly; see
                # ops.quantized.quantize_pages) — then attend over the
                # dequantized pool.  Both the flash and jnp paths run
                # through the self_attend hook so the quantize-then-
                # attend order (and hence the tokens) agree.
                from bigdl_tpu.ops.flash_attention import \
                    paged_decode_attention
                from bigdl_tpu.ops.quantized import quantize_pages

                kv = {"k": kv_k, "v": kv_v, "sk": kv_sk, "sv": kv_sv}
                B = lengths.shape[0]
                rows = jnp.arange(B)
                K = n_blocks * page
                h, hd = adapter.num_heads, adapter.head_dim

                def rmw(pool, scales, i, new):
                    floor = scales[i, wid]                      # (B,)
                    pg = (pool[i, wid].astype(jnp.float32)
                          * floor[:, None, None, None])      # (B,h,p,hd)
                    pg = pg.at[rows, :, off].set(new[:, :, 0])
                    q, s = quantize_pages(pg, floor_scales=floor)
                    return (pool.at[i, wid].set(q, mode="drop"),
                            scales.at[i, wid].set(s, mode="drop"))

                def self_attend(i, q, k_new, v_new):
                    kv["k"], kv["sk"] = rmw(kv["k"], kv["sk"], i, k_new)
                    kv["v"], kv["sv"] = rmw(kv["v"], kv["sv"], i, v_new)
                    if use_flash:
                        out = paged_decode_attention(
                            q[:, :, 0], kv["k"][i], kv["v"][i], pt,
                            lengths, k_scales=kv["sk"][i],
                            v_scales=kv["sv"][i])
                        return out.astype(jnp.float32)[:, :, None]
                    # gathered-jnp reference: dequantize this layer's
                    # pages and attend over the contiguous view — the
                    # kernel-vs-jnp agreement surface for int8
                    def deq(pool, scales):
                        g = (pool[i][pt].astype(jnp.float32)
                             * scales[i][pt][..., None, None, None])
                        return g.transpose(0, 2, 1, 3, 4).reshape(
                            B, h, K, hd)

                    valid = (jnp.arange(K)[None, :]
                             <= lengths[:, None])[:, None, :]
                    return adapter._attend(q, deq(kv["k"], kv["sk"]),
                                           deq(kv["v"], kv["sv"]),
                                           valid)

                logits, _, _, _, _ = adapter.chunk_forward(
                    adapter.params, last_tokens[:, None], lengths, None,
                    None, ctx_bufs, self_attend=self_attend)
                kv_k, kv_v = kv["k"], kv["v"]
                kv_sk, kv_sv = kv["sk"], kv["sv"]
            elif use_flash:
                # paged flash path: scatter each layer's K/V into the
                # pages FIRST, then run the single-query Pallas kernel
                # straight off the page pool — no gathered cache copy
                from bigdl_tpu.ops.flash_attention import \
                    paged_decode_attention

                kv = {"k": kv_k, "v": kv_v}

                def self_attend(i, q, k_new, v_new):
                    kv["k"] = kv["k"].at[i, wid, :, off].set(
                        k_new[:, :, 0].astype(kv_k.dtype), mode="drop")
                    kv["v"] = kv["v"].at[i, wid, :, off].set(
                        v_new[:, :, 0].astype(kv_v.dtype), mode="drop")
                    out = paged_decode_attention(
                        q[:, :, 0], kv["k"][i], kv["v"][i], pt, lengths)
                    return out.astype(jnp.float32)[:, :, None]

                logits, _, _, _, _ = adapter.chunk_forward(
                    adapter.params, last_tokens[:, None], lengths, None,
                    None, ctx_bufs, self_attend=self_attend)
                kv_k, kv_v = kv["k"], kv["v"]
            else:
                kbuf = self._gather(kv_k, pt)
                vbuf = self._gather(kv_v, pt)
                logits, _, _, k_new, v_new = adapter.chunk_forward(
                    adapter.params, last_tokens[:, None], lengths, kbuf,
                    vbuf, ctx_bufs)
                kv_k = kv_k.at[:, wid, :, off].set(
                    k_new[:, :, :, 0].astype(kv_k.dtype), mode="drop")
                kv_v = kv_v.at[:, wid, :, off].set(
                    v_new[:, :, :, 0].astype(kv_v.dtype), mode="drop")
            tok, logp = _select_tokens(logits[:, 0], keys, lengths + 1,
                                       temps, top_ks, top_ps)
            return kv_k, kv_v, kv_sk, kv_sv, tok, logp

        fn = adapter.jit(step, donate_argnums=(0, 1, 2, 3))
        self._step_fns[n_blocks] = fn
        return fn

    def _prefill_fn(self, n_blocks: int):
        """Prefill one chunk for up to ``prefill_batch`` slots in ONE
        program call: attends over the pages written so far, scatters
        every row's chunk K/V into its slot's pages, and selects the
        FIRST generated token from the logits at ``last_index`` (only
        meaningful for rows on their final chunk).  The batch is padded
        to exactly ``prefill_batch`` rows (inactive padding rows write
        nowhere) — one compiled program per cache bucket, and >= 2 rows
        keeps the bit-parity rule.  Per-row ``ctx`` arrives stacked
        (leading dim = prefill_batch)."""
        fn = self._prefill_fns.get(n_blocks)
        if fn is not None:
            return fn
        cfg = self.cfg
        adapter = self.adapter
        page = cfg.page_size
        C = cfg.prompt_chunk
        quant = self._quant_kv

        base_key = jnp.asarray(np.asarray(self._base_key))

        def prefill(kv_k, kv_v, kv_sk, kv_sv, ctx_bufs, slot_idx,
                    pt_rows, tokens, position, last_index, active, seeds,
                    temps, top_ks, top_ps):
            keys = jax.vmap(jax.random.fold_in)(
                jnp.broadcast_to(base_key, (seeds.shape[0], 2)), seeds)
            pt = pt_rows[:, :n_blocks]
            if quant:
                kbuf = self._gather_deq(kv_k, kv_sk, pt)
                vbuf = self._gather_deq(kv_v, kv_sv, pt)
            else:
                kbuf = self._gather(kv_k, pt)
                vbuf = self._gather(kv_v, pt)
            ctx = {k: v[slot_idx] for k, v in ctx_bufs.items()}
            logits, kbuf, vbuf, k_new, v_new = adapter.chunk_forward(
                adapter.params, tokens, position, kbuf, vbuf, ctx)
            last = jnp.take_along_axis(logits,
                                       last_index[:, None, None],
                                       axis=1)[:, 0]              # (B, V)
            sel_pos = position + last_index + 1
            tok, logp = _select_tokens(last, keys, sel_pos, temps,
                                       top_ks, top_ps)
            if quant:
                # whole-page requantize-write-back of ONLY the pages
                # this chunk touched: rows past a slot's allocated count
                # may reference pages another slot owns now (the table
                # is not cleared on release), and the leading rows may
                # be shared prefix-cache pages — neither may be written.
                # Untouched positions inside a touched page came from
                # the dequantized gather, so under the monotone scale
                # floor they requantize exactly (quantize_pages).
                from bigdl_tpu.ops.quantized import quantize_pages

                B = tokens.shape[0]
                L, h, hd = (adapter.num_layers, adapter.num_heads,
                            adapter.head_dim)
                pg0 = jnp.arange(n_blocks)[None, :] * page       # (1,nb)
                lim = jnp.minimum(position + C, cfg.cap)[:, None]
                mask = (active[:, None] & (pg0 < lim)
                        & (pg0 + page > position[:, None]))      # (B,nb)
                pidq = jnp.where(mask, pt, cfg.total_pages)
                floors_k = kv_sk[:, pt]                        # (L,B,nb)
                floors_v = kv_sv[:, pt]

                def wb(pool, scales, buf, floors):
                    pages = buf.reshape(B, L, h, n_blocks, page,
                                        hd).transpose(1, 0, 3, 2, 4, 5)
                    q, s = quantize_pages(pages, floor_scales=floors)
                    return (pool.at[:, pidq].set(q, mode="drop"),
                            scales.at[:, pidq].set(s, mode="drop"))

                kv_k, kv_sk = wb(kv_k, kv_sk, kbuf, floors_k)
                kv_v, kv_sv = wb(kv_v, kv_sv, vbuf, floors_v)
                return kv_k, kv_v, kv_sk, kv_sv, tok, logp
            # scatter each row's chunk into its pages; padding rows and
            # positions past the slot cap (padded final-chunk tails)
            # drop
            pos_c = position[:, None] + jnp.arange(C)[None, :]   # (B, C)
            pid = jnp.take_along_axis(
                pt_rows, jnp.clip(pos_c // page, 0,
                                  cfg.pages_per_slot - 1), axis=1)
            ok = active[:, None] & (pos_c < cfg.cap)
            pid = jnp.where(ok, pid, cfg.total_pages)
            off = pos_c % page
            # kv (L, P, h, page, hd) at [:, pid (B,C), :, off (B,C)]
            # -> (B, C, L, h, hd) value layout
            kv_k = kv_k.at[:, pid, :, off].set(
                k_new.transpose(0, 3, 1, 2, 4).astype(kv_k.dtype),
                mode="drop")
            kv_v = kv_v.at[:, pid, :, off].set(
                v_new.transpose(0, 3, 1, 2, 4).astype(kv_v.dtype),
                mode="drop")
            return kv_k, kv_v, kv_sk, kv_sv, tok, logp

        fn = adapter.jit(prefill, donate_argnums=(0, 1, 2, 3))
        self._prefill_fns[n_blocks] = fn
        return fn

    # -- speculative programs (docs/serving.md §Speculative decoding) -------
    def _draft_fn(self, n_blocks: int):
        """Draft ``k+1`` tokens per active slot with the block-sparse
        twin over the f32 draft page pool: gather the slot's draft
        cache once, ``lax.scan`` k+1 single-token steps through
        ``chunk_forward(model=draft)``, then scatter the chunk of fresh
        draft K/V back into the pool.  k+1 steps (not k) because step
        ``j`` writes draft KV at position ``lengths+j`` — the extra
        step fills the cache hole at ``lengths+k`` the full-accept
        bonus token needs on the NEXT iteration.  Selection goes
        through ``_select_tokens`` with the same keys/positions the
        verify uses, so at temperature>0 a close draft samples the same
        token (shared-Gumbel coupling) and acceptance stays high.

        With ``SpecConfig.draft_window=W`` (and a cache bucket wider
        than W) the scan carries a RING of the last W positions'
        draft K/V instead of the full gathered cache: slot ``q % W``
        holds position ``q``, each step overwrites one slot and
        attends the whole ring under a ``q >= 0`` mask.  The draft's
        per-step attention traffic is then O(W) however long the
        sequence grows — the asymmetry speculation lives on, since
        the target still re-reads its full cache but only once per
        k+1 tokens (the verify)."""
        fn = self._draft_fns.get(n_blocks)
        if fn is not None:
            return fn
        cfg = self.cfg
        adapter = self.adapter
        page = cfg.page_size
        k_spec = self._spec.k
        W = self._spec.draft_window
        windowed = W is not None and int(W) < n_blocks * page
        draft_model = self._draft_model
        base_key = jnp.asarray(np.asarray(self._base_key))

        def draft(dr_k, dr_v, page_table, lengths, last_tokens, active,
                  seeds, temps, top_ks, top_ps):
            B = lengths.shape[0]
            keys = jax.vmap(jax.random.fold_in)(
                jnp.broadcast_to(base_key, (B, 2)), seeds)
            pt = page_table[:, :n_blocks]
            if windowed:
                # seed ring slot j with the LAST cached position
                # congruent to j mod W (negative = not cached yet,
                # masked out at attend time)
                q_seed = ((lengths - 1)[:, None]
                          - ((lengths - 1)[:, None] - jnp.arange(W))
                          % W)                               # (B, W)
                cell = jnp.clip(q_seed, 0, cfg.cap - 1)
                pid = jnp.take_along_axis(
                    pt, jnp.clip(cell // page, 0, n_blocks - 1), axis=1)
                off = cell % page
                rows = jnp.arange(B)

                def seed(pool):
                    g = pool[:, pid, :, off]      # (B, W, L, h, hd)
                    return g.transpose(2, 0, 3, 1, 4)  # (L, B, h, W, hd)

                rk, rv = seed(dr_k), seed(dr_v)

                def body(carry, _):
                    rk, rv, pos, last = carry
                    ring = {"k": rk, "v": rv}
                    slot = pos % W
                    # slot j holds position pos - ((pos - j) % W); only
                    # q >= 0 rows are real (short sequences)
                    q_j = (pos[:, None]
                           - (pos[:, None] - jnp.arange(W)) % W)
                    ok = (q_j >= 0)[:, None, :]            # (B, 1, W)

                    def self_attend(i, q, k_new, v_new):
                        ring["k"] = ring["k"].at[i, rows, :, slot].set(
                            k_new[:, :, 0])
                        ring["v"] = ring["v"].at[i, rows, :, slot].set(
                            v_new[:, :, 0])
                        return adapter._attend(q, ring["k"][i],
                                               ring["v"][i], ok)

                    logits, _, _, k_new, v_new = adapter.chunk_forward(
                        adapter.params, last[:, None], pos, None, None,
                        {}, self_attend=self_attend, model=draft_model)
                    tok, _ = _select_tokens(logits[:, 0], keys, pos + 1,
                                            temps, top_ks, top_ps)
                    return ((ring["k"], ring["v"], pos + 1, tok),
                            (tok, k_new[:, :, :, 0], v_new[:, :, :, 0]))

                (_, _, _, _), (toks, k_news, v_news) = jax.lax.scan(
                    body, (rk, rv, lengths, last_tokens), None,
                    length=k_spec + 1)
            else:
                kbuf = self._gather(dr_k, pt)
                vbuf = self._gather(dr_v, pt)

                def body(carry, _):
                    kbuf, vbuf, pos, last = carry
                    logits, kbuf, vbuf, k_new, v_new = \
                        adapter.chunk_forward(
                            adapter.params, last[:, None], pos, kbuf,
                            vbuf, {}, model=draft_model)
                    tok, _ = _select_tokens(logits[:, 0], keys, pos + 1,
                                            temps, top_ks, top_ps)
                    return ((kbuf, vbuf, pos + 1, tok),
                            (tok, k_new[:, :, :, 0], v_new[:, :, :, 0]))

                (_, _, _, _), (toks, k_news, v_news) = jax.lax.scan(
                    body, (kbuf, vbuf, lengths, last_tokens), None,
                    length=k_spec + 1)
            # persist the fresh chunk into the draft pool with one
            # page-granular write (k_news (C, B, L, h, hd) -> the
            # helper's (L, B, h, C, hd) layout); inactive rows and
            # positions past the cap drop
            dr_k = self._write_chunk_pages(
                dr_k, jnp.transpose(k_news, (2, 1, 3, 0, 4)),
                page_table, lengths, active)
            dr_v = self._write_chunk_pages(
                dr_v, jnp.transpose(v_news, (2, 1, 3, 0, 4)),
                page_table, lengths, active)
            return dr_k, dr_v, jnp.moveaxis(toks, 0, 1)       # (B, C)

        fn = adapter.jit(draft, donate_argnums=(0, 1))
        self._draft_fns[n_blocks] = fn
        return fn

    def _verify_fn(self, n_blocks: int, force_scan: bool = False):
        """ONE target-model call scoring the whole drafted chunk
        ``[last_token, d_1..d_k]`` at positions ``[lengths..lengths+k]``
        and returning the target's selections for positions
        ``lengths+1..lengths+k+1`` — the tokens the spec-off engine
        would have emitted.

        Two tracings behind one signature, picked by
        ``SpecConfig.verify_impl``: the scan path runs k+1 single-token
        steps that mirror :meth:`_step_fn` OP-FOR-OP (same shapes, same
        pool writes, same selection call), so spec-on output is
        byte-identical to spec-off by construction — one dispatch
        replacing k+1 is where its speedup lives, not a changed
        computation.  The chunk path instead scatters the whole chunk's
        K/V and attends all k+1 queries in one multi-query pass
        (``paged_verify_attention`` on TPU, a gathered causal-staircase
        jnp attention elsewhere) — ~(k+1)x fewer ops, token-stream
        parity with logp allclose-not-bitwise, exactly like the
        spec-off flash path's own contract.  int8 KV always takes the
        scan path (page RMW is per-position).

        ``force_scan`` routes one iteration to the scan tracing even
        when chunk is configured: the chunk attention's last-ulp logit
        drift is harmless under greedy argmax but the top-k/top-p
        threshold masks are DISCONTINUOUS in it (a logit within an ulp
        of the kth value flips in or out of the candidate set), so any
        iteration with a sampled (temperature>0) slot takes the scan
        program and seeded parity stays unconditional.  Both tracings
        join warmup()'s closed set — the fallback is never a
        recompile."""
        cfg = self.cfg
        quant = self._quant_kv
        use_flash = self._use_flash()
        impl = self._spec.verify_impl
        chunk_mode = (not quant) and not force_scan and (
            use_flash if impl == "auto" else impl == "chunk")
        fn = self._verify_fns.get((n_blocks, chunk_mode))
        if fn is not None:
            return fn
        adapter = self.adapter
        page = cfg.page_size
        C = self._spec.k + 1
        base_key = jnp.asarray(np.asarray(self._base_key))

        def verify(kv_k, kv_v, kv_sk, kv_sv, page_table, last_tokens,
                   d_toks, lengths, active, seeds, temps, top_ks,
                   top_ps):
            # the verify row [t_L, d_0..d_{k-1}] is assembled ON DEVICE
            # from the draft program's output, so the engine can enqueue
            # this program without first syncing the draft tokens back
            # to the host — the two dispatches overlap with the host's
            # acceptance bookkeeping
            tokens = jnp.concatenate(
                [last_tokens[:, None].astype(jnp.int32),
                 d_toks[:, :C - 1]], axis=1)
            B = tokens.shape[0]
            keys = jax.vmap(jax.random.fold_in)(
                jnp.broadcast_to(base_key, (B, 2)), seeds)
            pt = page_table[:, :n_blocks]
            if chunk_mode:
                # multi-query chunk path: scatter the chunk's K/V into
                # the pages per layer, then verify straight off the
                # pool (ops.flash_attention.paged_verify_attention)
                from bigdl_tpu.ops.flash_attention import \
                    paged_verify_attention

                pos_c = lengths[:, None] + jnp.arange(C)[None, :]
                pid = jnp.take_along_axis(
                    page_table, jnp.clip(pos_c // page, 0,
                                         cfg.pages_per_slot - 1),
                    axis=1)
                ok = active[:, None] & (pos_c < cfg.cap)
                h, hd = adapter.num_heads, adapter.head_dim
                K = n_blocks * page

                if use_flash:
                    pid = jnp.where(ok, pid, cfg.total_pages)
                    off = pos_c % page
                    kv = {"k": kv_k, "v": kv_v}

                    def self_attend(i, q, k_new, v_new):
                        kv["k"] = kv["k"].at[i, pid, :, off].set(
                            k_new.transpose(0, 2, 1, 3).astype(
                                kv_k.dtype), mode="drop")
                        kv["v"] = kv["v"].at[i, pid, :, off].set(
                            v_new.transpose(0, 2, 1, 3).astype(
                                kv_v.dtype), mode="drop")
                        out = paged_verify_attention(
                            q, kv["k"][i], kv["v"][i], pt, lengths)
                        return out.astype(jnp.float32)

                    logits, _, _, _, _ = adapter.chunk_forward(
                        adapter.params, tokens, lengths, None, None,
                        {}, self_attend=self_attend)
                    out_k, out_v = kv["k"], kv["v"]
                else:
                    # jnp chunk: attend the in-flight chunk K/V from
                    # REGISTERS (old pool keys strictly pre-chunk, the
                    # chunk's own keys under a causal staircase),
                    # merging the two softmaxes flash-style rather than
                    # concatenating buffers (a concat materializes
                    # (B,h,C,K+C) copies per layer — measured, it
                    # dominated the call); no cell-granular pool
                    # scatter on the hot path either — the pool write
                    # happens ONCE below, page-granular
                    news = []
                    scale = 1.0 / np.sqrt(float(hd))
                    old_ok = (jnp.arange(K)[None, None, None, :]
                              < lengths[:, None, None, None])
                    stair = (jnp.arange(C)[None, :]
                             <= jnp.arange(C)[:, None])  # (C, C)

                    # contractions run with (b, h) flattened into one
                    # batch dim — XLA:CPU dispatches a (B*h)-batched
                    # 3D dot far better than the 4D einsum (2.2x at
                    # these shapes); the math is identical
                    dn_k = (((2,), (2,)), ((0,), (0,)))
                    dn_v = (((2,), (1,)), ((0,), (0,)))

                    def self_attend(i, q, k_new, v_new):
                        news.append((k_new, v_new))      # (B, h, C, hd)
                        kb = kv_k[i][pt].transpose(
                            0, 2, 1, 3, 4).reshape(B * h, K, hd)
                        vb = kv_v[i][pt].transpose(
                            0, 2, 1, 3, 4).reshape(B * h, K, hd)
                        qf = (q.astype(jnp.float32) * scale).reshape(
                            B * h, C, hd)
                        s_old = jnp.where(
                            old_ok,
                            jax.lax.dot_general(
                                qf, kb, dn_k,
                                preferred_element_type=jnp.float32
                            ).reshape(B, h, C, K),
                            _NEG_INF)
                        s_new = jnp.where(
                            stair[None, None],
                            jax.lax.dot_general(
                                qf, k_new.reshape(B * h, C, hd), dn_k,
                                preferred_element_type=jnp.float32
                            ).reshape(B, h, C, C),
                            _NEG_INF)
                        # each query attends at least its own chunk key
                        # (the staircase diagonal), so m is finite
                        m = jnp.maximum(s_old.max(-1, keepdims=True),
                                        s_new.max(-1, keepdims=True))
                        eo = jnp.exp(s_old - m)
                        en = jnp.exp(s_new - m)
                        den = (eo.sum(-1, keepdims=True)
                               + en.sum(-1, keepdims=True))
                        out = (jax.lax.dot_general(
                            eo.reshape(B * h, C, K), vb, dn_v,
                            preferred_element_type=jnp.float32)
                            + jax.lax.dot_general(
                                en.reshape(B * h, C, C),
                                v_new.reshape(B * h, C, hd), dn_v,
                                preferred_element_type=jnp.float32))
                        return out.reshape(B, h, C, hd) / den

                    logits, _, _, _, _ = adapter.chunk_forward(
                        adapter.params, tokens, lengths, None, None,
                        {}, self_attend=self_attend)
                    out_k = self._write_chunk_pages(
                        kv_k, jnp.stack([kn for kn, _ in news]),
                        page_table, lengths, active)
                    out_v = self._write_chunk_pages(
                        kv_v, jnp.stack([vn for _, vn in news]),
                        page_table, lengths, active)
                sel_pos = (pos_c + 1).reshape(-1)
                tok, logp = _select_tokens(
                    logits.reshape(B * C, -1),
                    jnp.repeat(keys, C, axis=0), sel_pos,
                    jnp.repeat(temps, C), jnp.repeat(top_ks, C),
                    jnp.repeat(top_ps, C))
                return (out_k, out_v, kv_sk, kv_sv,
                        tok.reshape(B, C), logp.reshape(B, C))

            # sequential-exact path: k+1 _step_fn bodies under one
            # lax.scan — fed tokens are the PREDETERMINED chunk, so
            # there is no data-dependent control flow to trace
            rows = jnp.arange(B)
            K = n_blocks * page
            h, hd = adapter.num_heads, adapter.head_dim

            def body(carry, tok_j):
                kv_k, kv_v, kv_sk, kv_sv, pos = carry
                wid = jnp.where(active,
                                jnp.take_along_axis(
                                    page_table, (pos // page)[:, None],
                                    axis=1)[:, 0],
                                cfg.total_pages)
                off = pos % page
                if quant:
                    from bigdl_tpu.ops.flash_attention import \
                        paged_decode_attention
                    from bigdl_tpu.ops.quantized import quantize_pages

                    kv = {"k": kv_k, "v": kv_v, "sk": kv_sk,
                          "sv": kv_sv}

                    def rmw(pool, scales, i, new):
                        floor = scales[i, wid]
                        pg = (pool[i, wid].astype(jnp.float32)
                              * floor[:, None, None, None])
                        pg = pg.at[rows, :, off].set(new[:, :, 0])
                        q, s = quantize_pages(pg, floor_scales=floor)
                        return (pool.at[i, wid].set(q, mode="drop"),
                                scales.at[i, wid].set(s, mode="drop"))

                    def self_attend(i, q, k_new, v_new):
                        kv["k"], kv["sk"] = rmw(kv["k"], kv["sk"], i,
                                                k_new)
                        kv["v"], kv["sv"] = rmw(kv["v"], kv["sv"], i,
                                                v_new)
                        if use_flash:
                            out = paged_decode_attention(
                                q[:, :, 0], kv["k"][i], kv["v"][i], pt,
                                pos, k_scales=kv["sk"][i],
                                v_scales=kv["sv"][i])
                            return out.astype(jnp.float32)[:, :, None]

                        def deq(pool, scales):
                            g = (pool[i][pt].astype(jnp.float32)
                                 * scales[i][pt][..., None, None, None])
                            return g.transpose(0, 2, 1, 3, 4).reshape(
                                B, h, K, hd)

                        valid = (jnp.arange(K)[None, :]
                                 <= pos[:, None])[:, None, :]
                        return adapter._attend(
                            q, deq(kv["k"], kv["sk"]),
                            deq(kv["v"], kv["sv"]), valid)

                    logits, _, _, _, _ = adapter.chunk_forward(
                        adapter.params, tok_j[:, None], pos, None,
                        None, {}, self_attend=self_attend)
                    kv_k, kv_v = kv["k"], kv["v"]
                    kv_sk, kv_sv = kv["sk"], kv["sv"]
                else:
                    kbuf = self._gather(kv_k, pt)
                    vbuf = self._gather(kv_v, pt)
                    logits, _, _, k_new, v_new = adapter.chunk_forward(
                        adapter.params, tok_j[:, None], pos, kbuf,
                        vbuf, {})
                    kv_k = kv_k.at[:, wid, :, off].set(
                        k_new[:, :, :, 0].astype(kv_k.dtype),
                        mode="drop")
                    kv_v = kv_v.at[:, wid, :, off].set(
                        v_new[:, :, :, 0].astype(kv_v.dtype),
                        mode="drop")
                tok, logp = _select_tokens(logits[:, 0], keys, pos + 1,
                                           temps, top_ks, top_ps)
                return ((kv_k, kv_v, kv_sk, kv_sv, pos + 1),
                        (tok, logp))

            carry, (toks, logps) = jax.lax.scan(
                body, (kv_k, kv_v, kv_sk, kv_sv, lengths),
                jnp.moveaxis(tokens, 0, 1))
            kv_k, kv_v, kv_sk, kv_sv, _ = carry
            return (kv_k, kv_v, kv_sk, kv_sv,
                    jnp.moveaxis(toks, 0, 1), jnp.moveaxis(logps, 0, 1))

        fn = adapter.jit(verify, donate_argnums=(0, 1, 2, 3))
        self._verify_fns[(n_blocks, chunk_mode)] = fn
        return fn

    def _draft_prefill_fn(self, n_blocks: int):
        """Mirror of the f32 prefill scatter for the DRAFT pool: the
        draft twin consumes each prompt chunk so a freshly admitted (or
        mid-flight) request has draft KV for its whole prompt before
        its first draft step.  No token selection — the first generated
        token is the TARGET prefill's, identical to spec-off.  A
        handoff-imported slot skips this (its draft pages stay cold:
        drafts start uninformed, acceptance recovers as positions
        fill in; correctness never depends on draft contents)."""
        fn = self._draft_prefill_fns.get(n_blocks)
        if fn is not None:
            return fn
        cfg = self.cfg
        adapter = self.adapter
        page = cfg.page_size
        C = cfg.prompt_chunk
        draft_model = self._draft_model

        def draft_prefill(dr_k, dr_v, pt_rows, tokens, position,
                          active):
            pt = pt_rows[:, :n_blocks]
            kbuf = self._gather(dr_k, pt)
            vbuf = self._gather(dr_v, pt)
            _, _, _, k_new, v_new = adapter.chunk_forward(
                adapter.params, tokens, position, kbuf, vbuf, {},
                model=draft_model)
            pos_c = position[:, None] + jnp.arange(C)[None, :]
            pid = jnp.take_along_axis(
                pt_rows, jnp.clip(pos_c // page, 0,
                                  cfg.pages_per_slot - 1), axis=1)
            ok = active[:, None] & (pos_c < cfg.cap)
            pid = jnp.where(ok, pid, cfg.total_pages)
            off = pos_c % page
            dr_k = dr_k.at[:, pid, :, off].set(
                k_new.transpose(0, 3, 1, 2, 4), mode="drop")
            dr_v = dr_v.at[:, pid, :, off].set(
                v_new.transpose(0, 3, 1, 2, 4), mode="drop")
            return dr_k, dr_v

        fn = adapter.jit(draft_prefill, donate_argnums=(0, 1))
        self._draft_prefill_fns[n_blocks] = fn
        return fn

    def _ctx_write(self):
        if self._ctx_write_fn is None:
            def write(bufs, slot, values):
                return {k: jax.lax.dynamic_update_slice(
                    bufs[k], values[k][None].astype(bufs[k].dtype),
                    (slot,) + (0,) * values[k].ndim)
                    for k in bufs}

            self._ctx_write_fn = jax.jit(write, donate_argnums=(0,))
        return self._ctx_write_fn

    def _import_write(self):
        """Scatter a handoff's host KV page images into the pool.  The
        host side is padded to a fixed ``pages_per_slot`` page count
        (surplus rows carry an out-of-range page id and drop), so every
        import — any prompt length — runs ONE compiled program: the
        closed-compile-set discipline holds across the fleet path."""
        if self._import_fn is None:
            def write(kv_k, kv_v, kv_sk, kv_sv, pids, k_host, v_host,
                      sk_host, sv_host):
                # (L, P, h, page, hd) at [:, pids (PPS,)] takes the
                # (L, PPS, h, page, hd) view the host image is shaped as
                kv_k = kv_k.at[:, pids].set(k_host.astype(kv_k.dtype),
                                            mode="drop")
                kv_v = kv_v.at[:, pids].set(v_host.astype(kv_v.dtype),
                                            mode="drop")
                kv_sk = kv_sk.at[:, pids].set(sk_host, mode="drop")
                kv_sv = kv_sv.at[:, pids].set(sv_host, mode="drop")
                return kv_k, kv_v, kv_sk, kv_sv

            self._import_fn = jax.jit(write,
                                      donate_argnums=(0, 1, 2, 3))
        return self._import_fn

    # -- engine loop --------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            occupied = any(s is not None for s in self._slots)
            with self._cv:
                if not self._heap and not occupied:
                    self._cv.wait(0.2)
                    continue
            try:
                with self._iter_lock:
                    now = time.time()
                    self._sweep_cancelled()
                    self._expire(now)
                    self._admit(now)
                    did = self._decode_step()
                    # one prefill call per EMITTED token, not per
                    # iteration: a speculative iteration advances the
                    # decode streams up to k+1 tokens, so a prefilling
                    # slot gets the same interleave bandwidth it would
                    # under plain decode — otherwise admission latency
                    # stretches by the whole chunk factor
                    for _ in range(1 if self._spec is None
                                   else self._spec.k + 1):
                        pf = self._prefill_one()
                        did = pf or did
                        if not pf:
                            break
                if not did:
                    # queued work blocked on slots/pages (or an empty
                    # beat between admission and prefill): wait for a
                    # release/submit notify instead of spinning
                    with self._cv:
                        self._cv.wait(0.05)
            except Exception as e:  # noqa: BLE001 — the engine must
                # outlive one bad batch: fail the in-flight requests
                # with an explicit verdict and keep serving
                log.error("decode engine iteration failed: %s", e,
                          exc_info=True)
                with self._iter_lock:
                    for s, seq in enumerate(self._slots):
                        if seq is not None:
                            self._finish_error(seq.req, e)
                            self._release_slot(s)

    def _expire(self, now: float) -> None:
        """Deadline enforcement at BOTH granularities: queued requests
        are dropped at slot pickup (the PR 8 discipline), and ACTIVE
        slots are re-checked per token so an expired streaming request
        frees its slot and pages immediately instead of decoding to
        ``max_new_tokens``."""
        expired_q = []
        with self._cv:
            # the heap is keyed by deadline, so expired requests sit at
            # the head — O(expired) per sweep, not O(queue)
            while self._heap and self._heap[0][0] <= now:
                expired_q.append(heapq.heappop(self._heap)[2])
        for req in expired_q:
            self.events.append(("expire_queued", req.rid))
            self._finish_expired(req, now)
        for s, seq in enumerate(self._slots):
            if seq is not None and not seq.done \
                    and seq.req.deadline_t <= now:
                self.events.append(("expire", seq.req.rid, s))
                self._finish_expired(seq.req, now, seq=seq)
                self._release_slot(s)

    def _pages_needed(self, prompt_len: int, max_new: int,
                      start: int = 0) -> int:
        """Worst-case page rows the slot's page table will reference.
        ``start`` is where prefill resumes (the prefix-cache attach
        length): chunks then run from ``start``, so the padded final
        chunk can reach past the cold padded extent — the reservation
        must cover it or a padded-tail scatter could pop an
        unreserved page."""
        cfg = self.cfg
        C = cfg.prompt_chunk
        # under speculation every iteration writes up to k positions
        # past the emitted length (draft lookahead + verify chunk), so
        # the worst-case reservation grows by k — admission-time
        # reservation is what keeps _ensure_pages infallible mid-flight
        spec_k = self._spec.k if self._spec is not None else 0
        padded_prompt = min(start + -(-(prompt_len - start) // C) * C,
                            cfg.cap)
        worst = min(max(padded_prompt, prompt_len + max_new + spec_k),
                    cfg.cap)
        return -(-worst // cfg.page_size)

    def _admit(self, now: float) -> None:
        cfg = self.cfg
        if not cfg.continuous and any(s is not None for s in self._slots):
            return   # whole-batch-restart baseline: wait for the gang
        while True:
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                return
            with self._cv:
                if not self._heap:
                    return
                d, _, req = heapq.heappop(self._heap)
                max_new = min(req.max_new_tokens or cfg.max_new_tokens,
                              cfg.cap - 1)
                self._cv.notify_all()
            try:
                if req.prepared is None:
                    # cache the prepared form ON the request: a page-
                    # pressure push-back must not re-run the adapter's
                    # prepare (for seq2seq that is a full encoder
                    # forward) on every engine iteration
                    req.prepared = self.adapter.prepare(req.tokens)
                prompt, ctx = req.prepared
            except Exception as e:  # noqa: BLE001 — bad request only
                self._finish_error(req, e)
                continue
            if len(prompt) == 0:
                self._finish_error(req, ValueError(
                    "adapter produced an empty decoder prompt"))
                continue
            max_new = min(max_new, cfg.cap - len(prompt))
            if max_new <= 0:
                self._finish_error(req, ValueError(
                    f"prompt of {len(prompt)} tokens leaves no room to "
                    f"generate within the cache cap {cfg.cap}"))
                continue
            cache = self._prefix_cache
            attach = None
            if cache is not None and req.handoff is None:
                attach = cache.match(prompt)
            shared = len(attach.pages) if attach is not None else 0
            attach_len = len(attach.key) if attach is not None else 0
            # owned pages only: the shared prefix rows are the cache's
            need = max(self._pages_needed(len(prompt), max_new,
                                          start=attach_len) - shared, 0)
            short = need - (len(self._free_pages) - self._reserved_pages)
            if short > 0 and cache is not None:
                # out of pages: reclaim idle cached prefixes (never a
                # page a live slot references — eviction skips entries
                # with attached slots, and the entry being attached
                # here is shielded)
                freed = cache.evict(short, protect=attach)
                if freed:
                    self._free_pages.extend(freed)
                    self.metrics.inc(
                        "serving.fleet.prefix_cache_evicted_pages",
                        len(freed))
            if len(self._free_pages) - self._reserved_pages < need:
                # not enough reservable pages: push back and wait for a
                # mid-flight release (ordering preserved — same key)
                with self._cv:
                    heapq.heappush(self._heap, (d, req.seq, req))
                return
            s = free[0]
            seq = _ActiveSeq(req, prompt, ctx, reserved=need,
                             max_new=max_new)
            self._reserved_pages += need
            self._slots[s] = seq
            self._lengths[s] = 0
            self._last_tokens[s] = 0
            self._active_mask[s] = False          # active once prefilled
            self._seeds[s] = np.int32(req.seed)
            self._temps[s] = np.float32(req.temperature)
            self._top_ks[s] = np.int32(req.top_k)
            self._top_ps[s] = np.float32(req.top_p)
            if attach is not None:
                # map the cached pages read-only into the leading page-
                # table rows; prefill resumes at the attach boundary
                # (strictly < len(prompt), so the first-token-selecting
                # final chunk always runs here).  Copy-on-extend: writes
                # only ever target rows >= len(shared)
                cache.attach(attach)
                seq.shared = list(attach.pages)
                seq.shared_entry = attach
                seq.prefill_pos = attach_len
                self._page_table[s, :shared] = attach.pages
                self.metrics.inc("serving.fleet.prefix_cache_hits")
                self.events.append(("prefix_attach", req.rid, s,
                                    attach_len))
            elif cache is not None and req.handoff is None:
                cache.record_miss()
                self.metrics.inc("serving.fleet.prefix_cache_misses")
            if ctx:
                vals = {k: v for k, v in ctx.items()}
                self._ctx_bufs = self._ctx_write()(self._ctx_bufs,
                                                   s, vals)
            self.stats["requests"] += 1
            self.metrics.inc("serving.decode.requests")
            self.events.append(("admit", req.rid, s))
            if req.handoff is not None:
                self._import_handoff(s, seq, req)
            tr = trace.active()
            if tr is not None:
                # submit -> slot claim: where a queued stream's time went
                # BEFORE any chip work (docs/observability.md §Decode
                # timelines); correlated by request_id like every
                # serving span
                tr.add_event("decode/admission", req.admit_t, time.time(),
                             request_id=req.rid, slot=s,
                             tenant=req.tenant)

    def _ensure_pages(self, s: int, upto_tokens: int) -> None:
        """Allocate pages for slot ``s`` covering cache positions
        ``[0, upto_tokens)`` — lazily, inside the admission-time
        reservation, so allocation can never fail mid-flight."""
        seq = self._slots[s]
        need = -(-min(upto_tokens, self.cfg.cap) // self.cfg.page_size)
        shared = len(seq.shared)   # prefix-cache rows lead the table
        while shared + len(seq.pages) < need:
            pid = self._free_pages.pop()
            self._reserved_pages -= 1
            self._page_table[s, shared + len(seq.pages)] = pid
            seq.pages.append(pid)
            if self._quant_kv:
                self._fresh_pages.append(pid)

    def _release_slot(self, s: int) -> None:
        seq = self._slots[s]
        if seq is None:
            return
        cache = self._prefix_cache
        pages = seq.pages
        if cache is not None and not seq.shared:
            # donate the page-aligned PROMPT prefix of a cold request:
            # positions < prefill_pos hold exact prompt K/V (decode
            # writes land at >= prompt_len, padded prefill tails at
            # >= prompt_len too), so whole covered pages are reusable
            # byte-for-byte by any prompt sharing the prefix.  Attached
            # requests don't donate — their prefix is already cached.
            n = min(seq.prefill_pos, len(seq.prompt)) \
                // self.cfg.page_size
            if n > 0 and cache.insert(
                    seq.prompt[:n * self.cfg.page_size], pages[:n],
                    page_dtype=self.cfg.kv_dtype):
                self.events.append(("prefix_donate", seq.req.rid, n))
                pages = pages[n:]   # ownership moved to the cache
        self._free_pages.extend(pages)
        self._reserved_pages -= max(seq.reserved - len(seq.pages), 0)
        if seq.shared_entry is not None:
            cache.detach(seq.shared_entry)
        self._slots[s] = None
        self._active_mask[s] = False
        self._lengths[s] = 0
        self.events.append(("release", seq.req.rid, s))
        with self._cv:
            self._cv.notify_all()

    # -- prefill ------------------------------------------------------------
    def _prefill_one(self) -> bool:
        """Run at most ONE prefill call per engine iteration — up to
        ``prefill_batch`` slots advance one chunk each.  The one-call-
        per-iteration interleave keeps a long prompt from ever stalling
        the decode batch; the co-batching keeps admission-heavy traffic
        from becoming dispatch-bound on prefill."""
        cfg = self.cfg
        cand = sorted(
            (self._slots[s].req.seq, s) for s in range(cfg.slots)
            if self._slots[s] is not None and self._slots[s].prefilling)
        if not cand:
            return False
        picked = [s for _, s in cand[:cfg.prefill_batch]]
        B, C = cfg.prefill_batch, cfg.prompt_chunk
        sc = self._prefill_scratch
        if sc is None:
            # jit copies host arrays to device at dispatch, so the
            # scratch block is safely reusable across calls
            sc = self._prefill_scratch = {
                "tokens": np.zeros((B, C), np.int32),
                "position": np.zeros((B,), np.int32),
                "last_index": np.zeros((B,), np.int32),
                "active": np.zeros((B,), bool),
                "seeds": np.zeros((B,), np.int32),
                "temps": np.zeros((B,), np.float32),
                "top_ks": np.zeros((B,), np.int32),
                "top_ps": np.ones((B,), np.float32),
                "slot_idx": np.zeros((B,), np.int32),
                "pt_rows": np.zeros((B, cfg.pages_per_slot), np.int32),
            }
        sc["tokens"][:] = 0
        sc["active"][:] = False
        rows = []              # (b, s, real, final)
        max_need = 1
        for b, s in enumerate(picked):
            seq = self._slots[s]
            p0 = seq.prefill_pos
            chunk = seq.prompt[p0:p0 + C]
            real = len(chunk)
            sc["tokens"][b, :real] = chunk
            sc["position"][b] = p0
            sc["last_index"][b] = real - 1
            sc["active"][b] = True
            sc["seeds"][b] = np.int32(seq.req.seed)
            sc["temps"][b] = seq.req.temperature
            sc["top_ks"][b] = seq.req.top_k
            sc["top_ps"][b] = seq.req.top_p
            sc["slot_idx"][b] = s
            self._ensure_pages(s, min(p0 + C, cfg.cap))
            sc["pt_rows"][b] = self._page_table[s]
            rows.append((b, s, real, (p0 + real) >= len(seq.prompt)))
            max_need = max(max_need, min(p0 + C, cfg.cap))
        nb = cfg.bucket_pages(max_need)
        self._flush_fresh_scales()
        t0 = time.time()
        kv_k, kv_v, kv_sk, kv_sv, tok, logp = self._prefill_fn(nb)(
            self._kv_k, self._kv_v, self._kv_sk, self._kv_sv,
            self._ctx_bufs, sc["slot_idx"], sc["pt_rows"], sc["tokens"],
            sc["position"], sc["last_index"], sc["active"], sc["seeds"],
            sc["temps"], sc["top_ks"], sc["top_ps"])
        self._kv_k, self._kv_v = kv_k, kv_v
        self._kv_sk, self._kv_sv = kv_sk, kv_sv
        if self._spec is not None:
            # the draft twin consumes the same chunk rows so its page
            # pool tracks the prompt position-for-position
            self._dr_k, self._dr_v = self._draft_prefill_fn(nb)(
                self._dr_k, self._dr_v, sc["pt_rows"], sc["tokens"],
                sc["position"], sc["active"])
        toks = np.asarray(tok)
        logps = np.asarray(logp, np.float32)
        now = time.time()
        self.stats["prefill_chunks"] += len(rows)
        self.metrics.inc("serving.decode.prefill_chunks", len(rows))
        self.events.append(("prefill_chunk",
                            [self._slots[s].req.rid for _, s, _, _
                             in rows]))
        tr = trace.active()
        for b, s, real, final in rows:
            seq = self._slots[s]
            if tr is not None:
                # one event per co-batched row: the rows share the wall
                # window of the single prefill call, each joined to its
                # own request by request_id
                tr.add_event("decode/prefill_chunk", t0, now,
                             request_id=seq.req.rid, slot=s,
                             chunk_start=seq.prefill_pos, tokens=real)
            seq.prefill_pos += real
            if final:
                self._lengths[s] = len(seq.prompt)
                self._emit_token(s, seq, int(toks[b]), logps[b], now)
        self.metrics.observe("serving.decode.prefill_s", now - t0)
        return True

    # -- decode -------------------------------------------------------------
    def _decode_step(self) -> bool:
        cfg = self.cfg
        if self._spec is not None:
            return self._spec_step()
        if not cfg.continuous and any(
                s is not None and s.prefilling for s in self._slots):
            # whole-batch-restart mode: the legacy scan only starts
            # once every prompt in the batch is processed — no decode
            # step may run until the whole wave finished prefill (or a
            # late-prefilling member would lose horizon steps)
            return False
        active = [s for s in range(cfg.slots) if self._active_mask[s]]
        occupied = [s for s in range(cfg.slots)
                    if self._slots[s] is not None]
        # whole-batch-restart mode: the wave steps the full horizon even
        # after every row finished (a fixed-length scan cannot exit
        # early) — finished rows ride along inactive, seats held
        static_wave = not cfg.continuous and occupied
        if not active and not static_wave:
            return False
        # chaos seam: a decode worker dying (os._exit) with streams in
        # flight — the pool proxy must fail the streams over
        faults.fire("fleet_worker_kill")
        for s in active:
            self._ensure_pages(s, int(self._lengths[s]) + 1)
        ref = active if active else occupied
        nb = cfg.bucket_pages(int(self._lengths[ref].max()) + 1)
        self._flush_fresh_scales()
        t0 = time.time()
        kv_k, kv_v, kv_sk, kv_sv, toks, logps = self._step_fn(nb)(
            self._kv_k, self._kv_v, self._kv_sk, self._kv_sv,
            self._ctx_bufs, self._page_table, self._lengths,
            self._last_tokens, self._active_mask, self._seeds,
            self._temps, self._top_ks, self._top_ps)
        self._kv_k, self._kv_v = kv_k, kv_v
        self._kv_sk, self._kv_sv = kv_sk, kv_sv
        toks = np.asarray(toks)
        logps = np.asarray(logps, np.float32)
        now = time.time()
        self.stats["steps"] += 1
        self.metrics.inc("serving.decode.steps")
        self.events.append(("decode_step", len(active), nb))
        if active and self._last_step_t:
            # every active slot streams one token per step, so the
            # inter-token latency of EVERY in-flight sequence is the
            # step gap — one observation per step, not one per token
            self.metrics.observe("serving.decode.inter_token_s",
                                 now - self._last_step_t)
        self._last_step_t = now
        n_tok = 0
        tr = trace.active()
        for s in active:
            seq = self._slots[s]
            self._lengths[s] += 1          # last_token's K/V just landed
            self._emit_token(s, seq, int(toks[s]), logps[s], now)
            if tr is not None:
                # per-token step event: every in-flight stream advanced
                # one token inside this step's wall window
                tr.add_event("decode/token_step", t0, now,
                             request_id=seq.req.rid, slot=s,
                             index=len(seq.generated) - 1)
            n_tok += 1
        self._tokens_window.append((now, n_tok))
        self.stats["tokens"] += n_tok
        self.metrics.inc("serving.decode.tokens_total", n_tok)
        self.metrics.observe("serving.decode.step_s", now - t0)
        if not cfg.continuous:
            if self._wave_steps == 0:
                # the wave's scan horizon: the longest member's request
                # (the legacy scan ran max_len steps for everyone; a
                # member asking for more than the config default must
                # not be truncated by its seat-mates)
                self._wave_horizon = max(
                    (s.max_new for s in self._slots if s is not None),
                    default=cfg.max_new_tokens)
            self._wave_steps += 1
            if self._wave_steps >= self._wave_horizon:
                # scan horizon reached: the whole wave restarts at once
                for s in range(cfg.slots):
                    seq = self._slots[s]
                    if seq is not None and not seq.done:
                        self._finish_ok(s, seq, "length")  # defensive
                    if self._slots[s] is not None:
                        self._release_slot(s)
                self._wave_steps = 0
        self._export_gauges(now)
        return True

    def _spec_step(self) -> bool:
        """One speculative iteration: draft k (+1 cache-filling) tokens
        with the sparse twin, verify the chunk with ONE target call,
        then accept the longest agreeing prefix on the host.  Emitted
        tokens are ALWAYS the verify's target selections — the drafted
        token at index j only gates whether the selection CONDITIONED
        on it (index j+1 onward) is usable — so the accepted stream is
        the spec-off stream by construction; speculation only changes
        how many tokens one iteration yields (1 mismatch-correction up
        to k+1 on full agreement, the bonus token included)."""
        cfg = self.cfg
        k = self._spec.k
        active = [s for s in range(cfg.slots) if self._active_mask[s]]
        if not active:
            return False
        faults.fire("fleet_worker_kill")
        for s in active:
            self._ensure_pages(s, min(int(self._lengths[s]) + 1 + k,
                                      cfg.cap))
        nb = cfg.bucket_pages(
            min(int(self._lengths[active].max()) + 1 + k, cfg.cap))
        self._flush_fresh_scales()
        t0 = time.time()
        dr_k, dr_v, d_toks = self._draft_fn(nb)(
            self._dr_k, self._dr_v, self._page_table, self._lengths,
            self._last_tokens, self._active_mask, self._seeds,
            self._temps, self._top_ks, self._top_ps)
        self._dr_k, self._dr_v = dr_k, dr_v
        # enqueue the verify BEHIND the still-running draft — it
        # consumes d_toks on device (the verify row is assembled inside
        # the program), so no host sync sits between the two dispatches.
        # Any sampled slot in the batch routes the iteration to the
        # scan tracing: top-k/top-p thresholds are discontinuous in
        # the chunk attention's ulp drift (see _verify_fn)
        sampled = bool(np.any(np.asarray(self._temps)[active] > 0.0))
        kv_k, kv_v, kv_sk, kv_sv, g_toks, g_logps = self._verify_fn(
            nb, force_scan=sampled)(
            self._kv_k, self._kv_v, self._kv_sk, self._kv_sv,
            self._page_table, self._last_tokens, d_toks, self._lengths,
            self._active_mask, self._seeds, self._temps, self._top_ks,
            self._top_ps)
        self._kv_k, self._kv_v = kv_k, kv_v
        self._kv_sk, self._kv_sv = kv_sk, kv_sv
        jax.block_until_ready(d_toks)   # draft done (verify may still run)
        t1 = time.time()
        d_host = np.asarray(d_toks)                          # (S, k+1)
        g_toks = np.asarray(g_toks)
        g_logps = np.asarray(g_logps, np.float32)
        now = time.time()
        self.stats["steps"] += 1
        self.metrics.inc("serving.decode.steps")
        self.metrics.observe("serving.decode.spec_draft_step_s",
                             t1 - t0)
        self.metrics.observe("serving.decode.spec_verify_step_s",
                             now - t1)
        self.events.append(("spec_step", len(active), nb))
        if self._last_step_t:
            # under speculation the step gap covers up to k+1 tokens
            # per stream — still the honest stream-stall figure
            self.metrics.observe("serving.decode.inter_token_s",
                                 now - self._last_step_t)
        self._last_step_t = now
        n_tok = 0
        drafted = accepted = rejected = 0
        tr = trace.active()
        for s in active:
            seq = self._slots[s]
            emitted = 0
            mismatch = False
            for j in range(k + 1):
                if j >= 1 and int(d_host[s, j - 1]) != int(
                        g_toks[s, j - 1]):
                    # the token fed at query j disagreed with the
                    # target's selection for that position (which was
                    # already emitted as the correction): everything
                    # from j on is conditioned on a token the target
                    # did not pick — stale pool K/V past ``lengths`` is
                    # overwritten before the next iteration attends
                    mismatch = True
                    break
                self._lengths[s] += 1   # the fed token's K/V landed
                self._emit_token(s, seq, int(g_toks[s, j]),
                                 g_logps[s, j], now)
                emitted += 1
                n_tok += 1
                if self._slots[s] is not seq or seq.done:
                    break               # eos / length freed the slot
            # accepted = draft tokens the target agreed with; rejected
            # = mismatch only (at most 1 per chunk — it ends the
            # chunk).  Drafts past an eos/length finish were never
            # adjudicated: they count as drafted (wasted work shows in
            # drafted - accepted - rejected) but not rejected, so a
            # dense twin (sparsity=0.0) pins acceptance at exactly 1.0
            acc = min(max(emitted - 1, 0), k)
            drafted += k
            accepted += acc
            rejected += 1 if mismatch else 0
            if tr is not None:
                tr.add_event("decode/spec_step", t0, now,
                             request_id=seq.req.rid, slot=s,
                             emitted=emitted, accepted=acc)
        self.stats["tokens"] += n_tok
        self.stats["spec_drafted"] += drafted
        self.stats["spec_accepted"] += accepted
        self.stats["spec_rejected"] += rejected
        self.metrics.inc("serving.decode.tokens_total", n_tok)
        self.metrics.inc("serving.decode.spec_drafted_tokens", drafted)
        self.metrics.inc("serving.decode.spec_accepted_tokens",
                         accepted)
        self.metrics.inc("serving.decode.spec_rejected_tokens",
                         rejected)
        self._accept_window.append((now, accepted, accepted + rejected))
        self._tokens_window.append((now, n_tok))
        self.metrics.observe("serving.decode.step_s", now - t0)
        self._export_gauges(now)
        return True

    def _emit_token(self, s: int, seq: _ActiveSeq, tok: int,
                    logp: np.float32, now: float) -> None:
        req = seq.req
        if not seq.generated:
            seq.first_token_t = now
            seq.first_logp = np.float32(logp)
            self.metrics.observe("serving.decode.ttft_s",
                                 now - req.admit_t)
        seq.last_token_t = now
        seq.generated.append(tok)
        seq.logp = np.float32(seq.logp + logp)
        seq.last_logp = np.float32(logp)
        if req.on_token is not None:
            try:
                req.on_token(req.rid, tok, len(seq.generated) - 1)
            except Exception:  # noqa: BLE001 — a slow/broken stream
                pass           # consumer must not kill the engine
        if tok == self.cfg.eos_id:
            self._finish_ok(s, seq, "eos")
        elif len(seq.generated) >= seq.max_new:
            self._finish_ok(s, seq, "length")
        else:
            self._last_tokens[s] = tok
            self._active_mask[s] = True

    def _finish_ok(self, s: int, seq: _ActiveSeq, reason: str) -> None:
        req = seq.req
        req.result = DecodeResult(
            tokens=np.asarray(seq.generated, np.int32),
            logp=float(seq.logp), prompt_len=len(seq.prompt),
            ttft_s=seq.first_token_t - req.admit_t,
            finish_reason=reason)
        if req.export_kv:
            # harvest BEFORE the slot releases its pages: copy the
            # prompt's KV page images to host for the fleet handoff
            self._harvest_kv(s, seq)
        self.stats["completed"] += 1
        self.metrics.inc("serving.decode.completed")
        tr = trace.active()
        if tr is not None:
            t = time.time()
            tr.add_event("decode/publish", t, t, request_id=req.rid,
                         finish_reason=reason,
                         tokens=len(seq.generated))
        if self.cfg.continuous:
            self._release_slot(s)
        else:
            # whole-batch-restart mode: the answer is out, but the SEAT
            # is held to the scan horizon — that is the baseline's cost
            seq.done = True
            self._active_mask[s] = False
        req._event.set()
        if req.on_done is not None:
            try:
                req.on_done(req)
            except Exception:  # noqa: BLE001
                pass

    def _harvest_kv(self, s: int, seq: _ActiveSeq) -> None:
        """Export side of the prefill/decode split: copy the pages
        covering the prompt to host, exactly as float32.  Reads shared
        prefix-cache rows too (read-only), so an attached prefill still
        exports a complete image."""
        cfg = self.cfg
        req = seq.req
        plen = len(seq.prompt)
        n = -(-plen // cfg.page_size)
        # fixed-width gather (surplus rows repeat page 0 and are sliced
        # off on host) so every export — any prompt length — reuses ONE
        # compiled gather: the closed-compile-set discipline again
        pids = np.zeros((cfg.pages_per_slot,), np.int32)
        pids[:n] = self._page_table[s, :n]
        # pages travel in their stored dtype (int8 handoffs are ~4x
        # smaller on the wire); int8 adds the per-(layer, page) scales
        k = np.asarray(self._kv_k[:, pids])[:, :n]
        v = np.asarray(self._kv_v[:, pids])[:, :n]
        req.kv_export = {
            "tokens": np.asarray(seq.prompt, np.int32),
            "first_token": int(seq.generated[0]),
            "first_logp": float(seq.first_logp),
            "temperature": float(req.temperature),
            "top_k": int(req.top_k),
            "top_p": float(req.top_p),
            "seed": int(req.seed),
            "request_id": req.rid,
            "kv_dtype": cfg.kv_dtype,
            "k": k,
            "v": v,
        }
        if self._quant_kv:
            req.kv_export["k_scales"] = np.asarray(
                self._kv_sk[:, pids], np.float32)[:, :n]
            req.kv_export["v_scales"] = np.asarray(
                self._kv_sv[:, pids], np.float32)[:, :n]
        self.stats["kv_exports"] += 1
        self.metrics.inc("serving.fleet.kv_exports")
        self.events.append(("kv_export", req.rid, int(n)))

    def _import_handoff(self, s: int, seq: _ActiveSeq,
                        req: DecodeRequest) -> None:
        """Decode side of the split: materialize pages for the prompt,
        scatter the transferred float32 images into them, and emit the
        prefill worker's first token.  The slot then decodes exactly as
        if the prefill had run locally — same pages-to-positions map,
        same bytes, same counter-based sampling keys."""
        cfg = self.cfg
        h = req.handoff
        plen = len(seq.prompt)
        n = -(-plen // cfg.page_size)
        self._ensure_pages(s, plen)
        self._flush_fresh_scales()
        pids = np.full((cfg.pages_per_slot,), cfg.total_pages, np.int32)
        pids[:n] = self._page_table[s, :n]
        a = self.adapter
        shape = (a.num_layers, cfg.pages_per_slot, a.num_heads,
                 cfg.page_size, a.head_dim)
        dt = np.int8 if self._quant_kv else np.float32
        k_host = np.zeros(shape, dt)
        v_host = np.zeros(shape, dt)
        k_host[:, :n] = np.asarray(h["k"], dt)
        v_host[:, :n] = np.asarray(h["v"], dt)
        sk_host = np.zeros((a.num_layers, cfg.pages_per_slot),
                           np.float32)
        sv_host = np.zeros_like(sk_host)
        if self._quant_kv:
            sk_host[:, :n] = np.asarray(h["k_scales"], np.float32)
            sv_host[:, :n] = np.asarray(h["v_scales"], np.float32)
        (self._kv_k, self._kv_v, self._kv_sk,
         self._kv_sv) = self._import_write()(
            self._kv_k, self._kv_v, self._kv_sk, self._kv_sv, pids,
            k_host, v_host, sk_host, sv_host)
        seq.prefill_pos = plen
        self._lengths[s] = plen
        self.stats["kv_imports"] += 1
        self.metrics.inc("serving.fleet.kv_imports")
        self.events.append(("kv_import", req.rid, s, int(n)))
        self._emit_token(s, seq, int(h["first_token"]),
                         np.float32(h["first_logp"]), time.time())

    def _finish_error(self, req: DecodeRequest, err: Exception) -> None:
        req.error = err
        req._event.set()
        if req.on_done is not None:
            try:
                req.on_done(req)
            except Exception:  # noqa: BLE001
                pass

    def _finish_expired(self, req: DecodeRequest, now: float,
                        seq: Optional[_ActiveSeq] = None) -> None:
        from bigdl_tpu.serving.server import DeadlineExceededError

        self.stats["expired"] += 1
        self.metrics.inc("serving.decode.expired")
        tr = trace.active()
        if tr is not None:
            tr.add_event("decode/publish", now, now, request_id=req.rid,
                         finish_reason="expired")
        err = DeadlineExceededError(req.rid, now - req.admit_t)
        if seq is not None and seq.generated:
            # a streaming request that already produced tokens: the
            # partial result rides on the error for the caller's framing
            err.partial_tokens = np.asarray(seq.generated, np.int32)
        self._finish_error(req, err)

    def _export_gauges(self, now: float) -> None:
        if now - self._gauge_t < 0.05:   # gauge freshness beats paying
            return                       # registry locks on every step
        self._gauge_t = now
        cfg = self.cfg
        self.metrics.gauge("serving.decode.slot_occupancy",
                           float(sum(s is not None for s in self._slots))
                           / cfg.slots)
        used = cfg.total_pages - len(self._free_pages)
        self.metrics.gauge("serving.decode.page_utilization",
                           used / cfg.total_pages)
        self.metrics.gauge("serving.decode.queue_depth",
                           self.queue_depth())
        # constant per engine, but exported so one scrape answers "what
        # does a page cost here" without reading config: int8 pools
        # report ~4x smaller pages (+ the per-page scale pair)
        self.metrics.gauge("serving.decode.kv_bytes_per_page",
                           float(self.kv_bytes_per_page()))
        if self._prefix_cache is not None:
            st = self._prefix_cache.stats()
            self.metrics.gauge("serving.fleet.prefix_cache_pages",
                               st["pages"])
            self.metrics.gauge("serving.fleet.prefix_cache_entries",
                               st["entries"])
        window = [(t, n) for t, n in self._tokens_window
                  if now - t <= 2.0]
        if len(window) >= 2:
            span = now - window[0][0]
            if span > 0:
                self.metrics.gauge("serving.decode.tokens_per_s",
                                   sum(n for _, n in window) / span)
        if self._spec is not None:
            w = [(t, a, d) for t, a, d in self._accept_window
                 if now - t <= 2.0]
            total = sum(d for _, _, d in w)
            if total:
                self.metrics.gauge(
                    "serving.decode.spec_accept_rate",
                    sum(a for _, a, _ in w) / total)

    # -- the one-scan whole-sequence parity reference -----------------------
    def static_generate(self, requests: Sequence[DecodeRequest]
                        ) -> List[DecodeResult]:
        """The byte-identical reference: each request decoded by the
        same chunked prefill followed by ONE ``lax.scan`` over a
        contiguous whole-sequence KV cache (no pages, no slots, no
        scheduling).  Mirrors the PR 8 ``continuous=False`` pattern:
        this path exists to pin the engine's numerics, not to be fast.

        Every request runs at batch 2 (the row duplicated) so every
        matmul keeps >= 2 rows — the same XLA reduction path the
        S-slot engine programs take (see the module docstring)."""
        out = []
        for req in requests:
            prompt, ctx = self.adapter.prepare(req.tokens)
            max_new = min(req.max_new_tokens or self.cfg.max_new_tokens,
                          self.cfg.cap - len(prompt))
            out.append(self._static_one(req, prompt, ctx, max_new))
        return out

    def _static_one(self, req: DecodeRequest, prompt: np.ndarray, ctx,
                    max_new: int) -> DecodeResult:
        cfg = self.cfg
        adapter = self.adapter
        L, h, hd = adapter.num_layers, adapter.num_heads, adapter.head_dim
        B = 2                                  # duplicated row (>= 2 rows)
        Kcap = cfg.cap
        kbuf = jnp.zeros((B, L, h, Kcap, hd), jnp.float32)
        vbuf = jnp.zeros_like(kbuf)
        ctx2 = {k: jnp.stack([v, v]) for k, v in (ctx or {}).items()}
        key = np.asarray(jax.random.fold_in(self._base_key,
                                            int(req.seed)), np.uint32)
        keys2 = jnp.asarray(np.stack([key, key]))
        temps = jnp.full((B,), req.temperature, jnp.float32)
        top_ks = jnp.full((B,), req.top_k, jnp.int32)
        top_ps = jnp.full((B,), req.top_p, jnp.float32)
        C = cfg.prompt_chunk
        first_tok = first_lp = None
        t_admit = time.time()
        for p0 in range(0, len(prompt), C):
            chunk = prompt[p0:p0 + C]
            real = len(chunk)
            if real < C:
                chunk = np.concatenate([chunk,
                                        np.zeros((C - real,), np.int32)])
            fn = self._static_prefill(C)
            kbuf, vbuf, tok, logp = fn(
                kbuf, vbuf, ctx2, jnp.asarray(np.stack([chunk, chunk])),
                jnp.full((B,), p0, jnp.int32),
                jnp.full((B,), real - 1, jnp.int32),
                keys2, temps, top_ks, top_ps)
            first_tok, first_lp = tok, logp
        scan = self._static_scan(max_new)
        toks, logps = scan(kbuf, vbuf, ctx2,
                           jnp.full((B,), len(prompt), jnp.int32),
                           first_tok, keys2, temps, top_ks, top_ps)
        toks = np.asarray(toks)[:, 0]           # (steps,) row 0
        logps = np.asarray(logps, np.float32)[:, 0]
        gen = [int(np.asarray(first_tok)[0])]
        total = np.float32(np.asarray(first_lp, np.float32)[0])
        reason = "length"
        if gen[0] == cfg.eos_id:
            reason = "eos"
        else:
            for t, lp in zip(toks, logps):
                gen.append(int(t))
                total = np.float32(total + lp)
                if int(t) == cfg.eos_id:
                    reason = "eos"
                    break
                if len(gen) >= max_new:
                    break
        return DecodeResult(tokens=np.asarray(gen, np.int32),
                            logp=float(total), prompt_len=len(prompt),
                            ttft_s=time.time() - t_admit,
                            finish_reason=reason)

    def _static_prefill(self, C: int):
        key = (C, 0)
        fn = self._static_prefill_fns.get(key)
        if fn is not None:
            return fn
        adapter = self.adapter
        cap = self.cfg.cap

        def prefill(kbuf, vbuf, ctx, tokens, position, last_index, keys,
                    temps, top_ks, top_ps):
            logits, kbuf, vbuf, _, _ = adapter.chunk_forward(
                adapter.params, tokens, position, kbuf, vbuf, ctx)
            last = jnp.take_along_axis(logits, last_index[:, None, None],
                                       axis=1)[:, 0]
            tok, logp = _select_tokens(last, keys,
                                       position + last_index + 1,
                                       temps, top_ks, top_ps)
            return kbuf, vbuf, tok, logp

        fn = adapter.jit(prefill)
        self._static_prefill_fns[key] = fn
        return fn

    def _static_scan(self, max_new: int):
        fn = self._static_scan_fns.get(max_new)
        if fn is not None:
            return fn
        adapter = self.adapter
        eos = self.cfg.eos_id

        def run(kbuf, vbuf, ctx, position, first_tok, keys, temps,
                top_ks, top_ps):
            def body(carry, _):
                kbuf, vbuf, pos, last, done, = carry
                logits, kbuf, vbuf, _, _ = adapter.chunk_forward(
                    adapter.params, last[:, None], pos, kbuf, vbuf, ctx)
                tok, logp = _select_tokens(logits[:, 0], keys, pos + 1,
                                           temps, top_ks, top_ps)
                tok = jnp.where(done, eos, tok)
                logp = jnp.where(done, 0.0, logp)
                done = done | (tok == eos)
                return (kbuf, vbuf, pos + 1, tok, done), (tok, logp)

            done0 = first_tok == eos
            (_, _, _, _, _), (toks, logps) = jax.lax.scan(
                body, (kbuf, vbuf, position, first_tok, done0),
                None, length=max(max_new - 1, 0))
            return toks, logps

        fn = adapter.jit(run)
        self._static_scan_fns[max_new] = fn
        return fn
