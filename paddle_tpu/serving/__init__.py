"""paddle_tpu.serving — production inference: paged KV pool + continuous
batching over the decode kernels, with the resilience layer that survives
the traffic the north star describes.

The serving half of the reference's fusion set rebuilt TPU-native
(`masked_multihead_attention_kernel.cu` → the Pallas decode kernel with the
aliased in-place cache append, `block_multi_head_attention_kernel.cu` →
:class:`PagedKVPool` page arenas, the `fused_multi_transformer` loop →
:class:`ServingEngine`'s two compiled programs), plus the production
surface: per-request SLO metrics (:class:`SLOMeter`: TTFT, TPOT, p50/p99
latency, queue depth, KV-pool occupancy, shed/deadline-miss rates) through
telemetry, a donation lint gate (:func:`check_decode_donation`) proving
the compiled decode program updates its cache in place, and the ISSUE-10
resilience layer: admission control (:class:`AdmissionController` —
bounded queue, :class:`Deadline` budgets, deadline shedding,
:class:`CircuitBreaker`), crash recovery (:class:`ServingJournal` +
:class:`TokenSink` — exactly-once delivery across a Supervisor relaunch),
and a decode-loop watchdog.

    engine = ServingEngine(model, max_batch=8, journal=jdir,
                           on_token=TokenSink(out_path))
    engine.recover()                # replay a crashed predecessor, if any
    rid = engine.submit(prompt_ids, max_new_tokens=64, eos_token_id=2,
                        deadline=Deadline(ttft_s=2.0, total_s=30.0))
    outputs = engine.run()          # {rid: generated token array}
    engine.meter.summary()          # ttft_ms_p99, deadline_miss_rate, ...

ISSUE-12 scales this to a FLEET: :class:`ServingFrontend` routes across N
replicas (:class:`Router` — least-loaded, deadline-aware spill), replica
membership rides heartbeat leases, every replica ships its journal to the
launcher's depot at the flush boundary that gates emission, and a dead
replica's work is fenced, folded and replayed on survivors with delivered
high-water marks primed — exactly-once tokens across replica death (see
:mod:`.fleet`).

ISSUE-19 disaggregates: TP-sharded decode (:func:`decode_mesh` +
:func:`shard_llama_params` partition the decode program and its paged KV
arenas over a ``model`` mesh axis), a dedicated prefill tier
(:class:`PrefillWorker` streams finished KV pages to decode replicas
through the journal depot with the same fence/epoch exactly-once
machinery), and a :class:`PrefixCache` (radix index over KV-pool pages
with copy-on-write refcounts — shared prompt prefixes skip re-prefill,
token-exact).

ISSUE-20 serves LONG context: a context-parallel prefill program shards a
long prompt's sequence dim over a ``sep`` ring mesh (``cp=N`` /
``PADDLE_TPU_SERVE_CP`` — one ring forward replaces the chunk-by-chunk
prefill loop, KV landing in the page arenas token-exact), cold requests
spill their KV pages to a host-RAM :class:`OffloadPool` tier under pool
pressure and resume decode after recall with ZERO recompute
(``offload=True`` / ``PADDLE_TPU_KV_OFFLOAD``; LRU-dropped frames
downgrade to the eviction-replay re-prefill — the "offload stall" row),
and ``kv_dtype="fp8"`` stores f8e4m3fn pages under one static scale at
exactly half the bf16 page bytes."""

from .kv_pool import (LatentLayersUnsupported, OffloadPool,  # noqa: F401
                      PagedKVPool, PassesUnsupported, PoolExhausted,
                      TRASH_PAGE, default_offload_pages,
                      default_page_tokens)
from .kv_quant import (FP8_MAX, KV_DTYPES, default_fp8_scale,  # noqa: F401
                       dequantize_kv, dequantize_kv_fp8, kv_cache_dtype,
                       kv_page_bytes, kv_scale_page_bytes, layer_page_bytes,
                       observe_kv_absmax, quantize_kv, quantize_kv_fp8)
from ..models.serve_protocol import (AttentionLayer,  # noqa: F401
                                     LatentAttentionLayer, StateLayer,
                                     StatelessLayer)
from .state_pool import RowStatePool, StateLayersUnsupported  # noqa: F401
from .metrics import FleetMeter, RequestClock, SLOMeter  # noqa: F401
from .admission import (AdmissionController, CircuitBreaker, Deadline,  # noqa: F401
                        Overloaded)
from .journal import JournalState, ServingJournal, TokenSink  # noqa: F401
from .engine import Request, ServingEngine, check_decode_donation  # noqa: F401
from .router import ReplicaStatus, Router  # noqa: F401
from .fleet import (EngineReplica, LocalKV, RemoteReplica,  # noqa: F401
                    ReplicaFlags, ReplicaServer, ServingFrontend,
                    TokenCollector, fold_depot_journal, run_replica)
from .autoscaler import (Autoscaler, AutoscalePolicy,  # noqa: F401
                         FleetSignals)
from .prefix_cache import PrefixCache, default_prefix_pages  # noqa: F401
from .disagg import (DisaggCoordinator, PrefillWorker,  # noqa: F401
                     decode_mesh, default_min_prompt, pack_kv_frame,
                     shard_arenas, shard_llama_params, take_prefilled,
                     unpack_kv_frame)

__all__ = [
    "PagedKVPool", "PoolExhausted", "TRASH_PAGE", "default_page_tokens",
    "OffloadPool", "default_offload_pages",
    "AttentionLayer", "LatentAttentionLayer", "StateLayer",
    "StatelessLayer", "RowStatePool",
    "StateLayersUnsupported", "LatentLayersUnsupported",
    "PassesUnsupported",
    "KV_DTYPES", "kv_cache_dtype", "quantize_kv", "dequantize_kv",
    "quantize_kv_fp8", "dequantize_kv_fp8", "default_fp8_scale", "FP8_MAX",
    "observe_kv_absmax", "kv_page_bytes", "kv_scale_page_bytes",
    "layer_page_bytes", "RequestClock", "SLOMeter", "FleetMeter",
    "AdmissionController", "CircuitBreaker", "Deadline", "Overloaded",
    "JournalState", "ServingJournal", "TokenSink",
    "Request", "ServingEngine", "check_decode_donation",
    "ReplicaStatus", "Router",
    "EngineReplica", "LocalKV", "RemoteReplica", "ReplicaFlags",
    "ReplicaServer", "ServingFrontend", "TokenCollector",
    "fold_depot_journal", "run_replica",
    "Autoscaler", "AutoscalePolicy", "FleetSignals",
    "PrefixCache", "default_prefix_pages",
    "DisaggCoordinator", "PrefillWorker", "decode_mesh",
    "default_min_prompt", "pack_kv_frame", "unpack_kv_frame",
    "shard_arenas", "shard_llama_params", "take_prefilled",
]
