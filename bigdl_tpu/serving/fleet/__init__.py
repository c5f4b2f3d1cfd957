"""Decode fleet — disaggregated prefill/decode serving over the pool.

The decode engine (docs/serving.md §Autoregressive decode) is
single-host: ``enqueue_generate`` binds each request to one worker's
engine, so admission pressure can become the wall under load (not
measured on the chip; PERF.md §7).  This package scales generation across
the multi-worker :class:`~bigdl_tpu.serving.pool.ServingPool`:

- :class:`~bigdl_tpu.serving.fleet.router.FleetRouter` — KV-aware
  placement of ``/generate`` over the decode-pressure signals workers
  report in ``/health`` (free slots, free pages, prefill backlog,
  ``slo_health``), replacing round-robin for the generate path.
- :mod:`~bigdl_tpu.serving.fleet.handoff` — the serialized page-transfer
  channel of the physical prefill/decode split: a dedicated prefill
  worker (``role="prefill"``) chunks the prompt, selects the first
  token, and ships the finished KV pages to a decode worker as an exact
  float32 byte image, so the continuation is byte-identical to having
  prefilled locally.
- :class:`~bigdl_tpu.serving.fleet.prefix_cache.PrefixCache` — per-worker
  reuse of KV pages for shared token prefixes (system prompts): the
  common prefix is prefilled once, later requests attach to the cached
  pages copy-on-extend, with hit/miss counters and LRU eviction bounded
  by the engine's page pool.

Everything here preserves the engine's byte-identical-to-
``static_generate`` parity invariant; tests/test_fleet.py proves it for
cached-prefix attach and cross-worker prefill→decode handoff.
"""

from bigdl_tpu.serving.fleet.handoff import (HandoffError, pack_handoff,
                                             unpack_handoff)
from bigdl_tpu.serving.fleet.prefix_cache import PrefixCache
from bigdl_tpu.serving.fleet.router import FleetRouter

__all__ = ["FleetRouter", "HandoffError", "PrefixCache", "pack_handoff",
           "unpack_handoff"]
