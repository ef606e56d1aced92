"""Performance variables + software performance counters (SPC).

Reference: opal/mca/base/mca_base_pvar.c (MPI_T performance variables) and
ompi/runtime/ompi_spc.h:46-153 (the ~110-counter SPC enum recorded via
SPC_RECORD() in the API layer and exported as MPI_T pvars). Here a single
process-wide counter table serves both roles; the MPI_T-style session API is
:func:`session` / ``read``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict

_counters: Dict[str, int] = {}
_watermarks: Dict[str, int] = {}
_timers: Dict[str, float] = {}
_lock = threading.Lock()
#: pvar.captured: the captures open in the process (record looks for
#: its thread's only while there is one) and each thread's table
_capturing = 0
_capture = threading.local()

# Counter names mirror the reference SPC set where it applies
# (ompi/runtime/ompi_spc.h): send/recv counts, bytes, collective op counts,
# unexpected/out-of-sequence message counts, time in progress, etc.
WELL_KNOWN = (
    "send", "isend", "recv", "irecv", "bytes_sent", "bytes_received",
    "unexpected", "out_of_sequence", "matched_probes",
    "allreduce", "bcast", "reduce", "allgather", "alltoall", "barrier",
    "reduce_scatter", "gather", "scatter", "scan", "exscan",
    "allreduce_xla", "bcast_xla", "allgather_xla", "alltoall_xla",
    "reduce_scatter_xla",
    # coll/xla dispatch + fusion counters (one compiled-program launch
    # each; the fused path's regression tests assert on these)
    "coll_xla_launches", "coll_xla_cache_hits", "coll_xla_cache_misses",
    "coll_xla_fused_bytes", "coll_xla_plan_cache_hits",
    "coll_xla_plan_cache_misses", "coll_xla_device_put_skipped",
    "coll_xla_cache_evictions",
    # calls of _Ctx.to_global that still dispatched an eager program
    # (and copied the operand) to build the global view: 0-d operands
    # only — an operand with a dimension to shard is viewed in place
    "coll_xla_global_view_copies",
    # a program's FIRST launch, where jax compiles it or loads it from
    # the persistent cache (always timed: once per cache key)
    "coll_xla_cold_launches", "coll_xla_cold_launch_ns",
    # ops/attention.attention, once per TRACED attention (the choice
    # is static inside jit): the blockwise kernel, or att.mha
    "attn_blockwise_layers", "attn_reference_layers",
    # the same, once per TRACED attention that took a segment mask
    # (whichever of the two ways it went)
    "attn_segment_layers",
    # the same, once per TRACED attention under a segment mask that
    # took the repo's own kernels (ops/segment_attention.py); it counts
    # among attn_blockwise_layers too
    "attn_segment_kernel_layers",
    # ops/moe.sorted_moe_ffn, once per TRACED MoE layer: its grouped
    # matmuls are the Pallas kernels, or lax.ragged_dot
    "moe_grouped_kernel_layers", "moe_ragged_dot_layers",
    # the same, once per TRACED MoE layer: the layer carries a bounded
    # number of rows (ops/moe.held_rows_bound: the chip holds a share
    # of the experts) with the full path as its fallback, or all T * k
    # rows and no second path; and, from the set-up probe
    # transformer.route_counts, the layer-batches whose held
    # assignments exceed the bound: how often the fallback would run
    "moe_bounded_layers", "moe_full_layers", "moe_over_bound_layers",
    # the same, once per TRACED bounded layer: a token's rows are
    # fetched by the sort's inverse and added, or summed by a 0/1
    # product on the MXU (the rule ops/moe.row_sum_gathers)
    "moe_row_sum_gather_layers", "moe_row_sum_product_layers",
    # the same, once per TRACED layer that fetches a token's rows by
    # the sort's inverse (the full layer; a bounded one on the gather's
    # side): the sums are ops/grouped_matmul.row_reduce's, one DMA a
    # held row from the packed layout the grouped matmuls write, or
    # XLA's gather and reduction (the rule ops/moe.row_reduce_kernel)
    "moe_row_reduce_kernel_layers", "moe_row_reduce_xla_layers",
    # models/transformer.py, once per TRACED layer: latent attention
    # (MLA); of those, the layers whose sparse-attention indexer
    # selects (the sequence is longer than index_topk)
    "attn_mla_layers", "attn_dsa_layers",
    # of the latent-attention layers, those whose query is one product
    # of x (no query latent: q_lora_rank 0)
    "attn_mla_plain_q_layers",
    # models/vision.py, per TRACED step of a config with a tower: the
    # patches of the packed row, and the positions of the sequence
    # their merged rows replace; the set-up probe vision.vision_stats:
    # the images of the batch it read, and the (query, key) pairs of
    # the block diagonal against all pairs of the packed row
    "vision_patches", "vision_image_positions", "vision_images",
    "vision_diag_pairs", "vision_row_pairs",
    # ops/attention.dsa_attend, once per TRACED call: the Pallas
    # kernels of ops/sparse_attention.py, or the masked blocks
    "attn_dsa_kernel_layers", "attn_dsa_masked_layers",
    # the set-up probes transformer.dsa_selection / route_counts: the
    # (query, key) pairs attention keeps of the causal ones, and the
    # token-expert assignments that fell to the experts this chip holds
    "dsa_selected_pairs", "dsa_causal_pairs", "moe_held_assignments",
    # models/transformer.py: once per TRACED pass over the layer list,
    # and per TRACED application of a layer (layers x passes); the
    # set-up probe transformer.exit_stats: the labelled positions it
    # read (the summed probability of exit s rides the dynamic name
    # exit_mass_micro_p<s>, in millionths of a token)
    "loop_passes", "loop_layer_applications", "exit_probe_tokens",
    # models/remat.Recomputed, once per TRACED application of a
    # recomputed layer (Config.remat): its backward pass is given what
    # the application made under the names the rule remat_keep chose,
    # or recomputes it whole from its input (no name fits, or the
    # device states no memory limit); and the bytes the rule reckons
    # the kept names hold, summed over the traced applications
    "remat_kept_applications", "remat_whole_applications",
    "remat_kept_bytes",
    # models/transformer.py, once per TRACED layer of a pattern: a
    # Mamba-2 state-space layer and the chunks its scan works a
    # sequence in (ops/ssm.py); an attention layer whose query heads
    # share fewer key heads; the set-up probe transformer.ssm_probe:
    # the norm of the first state-space layer's state after the last
    # token, in millionths
    "ssm_layers", "ssm_chunks", "attn_gqa_layers", "ssm_state_norm_micro",
    # models/transformer.py, once per TRACED layer of a config that
    # mixes kinds of attention (Config.attn_layers): a layer under the
    # sliding window, a full one; ops/attention.attention, once per
    # TRACED attention under a window that took the blockwise kernels:
    # the (query tile, key tile) pairs they walk, and what the causal
    # triangle would be at that tile (ops/attention.window_tiles)
    "attn_window_layers", "attn_full_layers", "attn_window_tiles",
    "attn_causal_tiles",
    # models/transformer.py, once per TRACED attention layer: its q and
    # k are normed per head (Config.qk_norm "head"); it takes no
    # rotation in a config whose other kind of attention rotates
    # (Config.rope_full / rope_window NO_ROPE); and once per TRACED
    # multi-token-prediction module of a config that mixes kinds of
    # attention, by the kind Config.mtp_attn gives it
    "attn_head_norm_layers", "attn_unrotated_layers", "mtp_full_layers",
    "mtp_window_layers",
    # ops/ssm.mixer, once per TRACED call: its scan runs on the Pallas
    # kernels of ops/ssm_scan.py, or as jax.numpy's batched products
    # (the rule ops/ssm.scan_tile)
    "ssm_scan_kernel_layers", "ssm_scan_product_layers",
    # ops/ssm.causal_conv, once per TRACED call (a Mamba-2 mixer's one,
    # a delta-rule run of heads' three): the convolution runs on the
    # Pallas kernels of ops/causal_conv.py, or as jax.numpy's K shifted
    # sums (the rule ops/ssm.conv_tile)
    "conv_kernel_layers", "conv_shifted_layers",
    # models/transformer.py, once per TRACED delta-rule layer
    # (Config.attn_layers 'd') and the chunks its recurrence works a
    # sequence in; ops/kda.mixer, once per TRACED call: the
    # chunk-to-chunk carry runs on the Pallas kernels, or as a lax.scan
    # (the rule ops/kda.carry_tile), and the chunk-local work ran in
    # those kernels too; an attention layer whose output is
    # gated (Config.attn_gate); the set-up probe transformer.kda_probe:
    # the norm of the probed layer's state after the last token, in
    # millionths
    "kda_layers", "kda_chunks", "kda_carry_kernel_layers",
    "kda_carry_scan_layers", "kda_core_kernel_layers", "attn_gated_layers",
    "kda_state_norm_micro",
    # the phases of mpi.Init(), once per job (runtime/state.py,
    # runtime/device_plane.py; "import" also holds the import of
    # ompi_tpu.mpi itself): they end before any profiler session can
    # exist, so the always-on counters are their only record
    "init_import_ns", "init_rte_ns", "init_accelerator_ns",
    "init_distributed_ns", "init_client_ns", "init_fence_ns",
    "init_pml_ns", "init_world_ns",
    # part/ (MPI-4 partitioned communication): host p2p epoch starts +
    # Pready/Parrived traffic; device Pallreduce bucket flushes, with
    # overlap_flushes counting buckets dispatched BEFORE the cycle's
    # final Pready (the overlap the subsystem exists for — the
    # partitioned regression tests assert on these)
    "part_send_start", "part_recv_start", "part_pready",
    "part_parrived", "part_bucket_flushes", "part_overlap_flushes",
    # zero/ (ZeRO sharded data parallel): fused reduce_scatter /
    # allgather bucket launches (the launch bound the zero tests
    # assert: ceil(total/bucket_bytes)+n_dtypes per direction per
    # cycle), bytes moved through the fused cycle, pad waste from
    # rounding buckets up to a multiple of comm size, and partitioned
    # buckets dispatched before the cycle's final Pready
    "zero_rs_launches", "zero_ag_launches", "zero_fused_bytes",
    "zero_pad_bytes", "zero_overlap_flushes",
    # stage-1/2 allgather dirty-skip: buckets whose shards did not
    # change this step (frozen leaves) reuse the previous cycle's
    # gathered leaves instead of relaunching
    "zero_ag_skipped",
    # zero-3 parameter stream: prefetch accounting (hit = the
    # layer-ahead gather was already issued when the consumer
    # arrived; late_ns = wall blocked on a prefetched-but-unfinished
    # gather), layer gather/release traffic, fused gather→matmul
    # consumptions, and the residency watermarks the O(1/n)+window
    # claim is asserted against
    "zero_prefetch_hits", "zero_prefetch_misses",
    "zero_prefetch_late_ns", "zero3_gathers", "zero3_releases",
    "zero3_fused_matmuls", "zero3_resident_bytes",
    "zero3_shard_bytes", "zero3_layer_bytes",
    "put", "get", "accumulate", "win_lock",
    "eager", "rndv", "rget",
    "time_progress_ns",
    # trace/ plane: spans lost to ring-buffer overflow; per-(op,
    # size-bin) log2 latency histograms ride dynamic names
    # (trace_hist_<op>_sz<s>_lat<l>, decoded by trace.export)
    "trace_dropped",
    # telemetry/ plane: collective flight-recorder entries, sampler
    # ticks + cost, watchdog sweeps and hang verdicts dumped
    "telemetry_flight_ops", "telemetry_samples",
    "telemetry_sample_ns", "telemetry_watchdog_sweeps",
    "telemetry_hangs",
    # prof/ plane (wall-clock attribution): phase-ledger wall per
    # canonical phase, host<->device transfer bytes + time per
    # direction (bandwidth hwm gauges ride prof_xfer_*_bw_mbps_hwm),
    # and jax's persistent compilation cache hits/misses over every
    # program (compile_cache_dir cvar)
    "prof_phase_staging_ns", "prof_phase_compile_ns",
    "prof_phase_train_ns", "prof_phase_teardown_ns",
    # the async checkpoint plane's d2h thread runs under "snapshot";
    # snapshot || train overlap accrues into prof_phase_overlap_ns
    # (the proof the ckpt smoke lane asserts on)
    "prof_phase_snapshot_ns",
    # zero-3 blocked prefetch waits run under "prefetch" — train-loop
    # wall lost to gathers the layer-ahead scheduler failed to hide
    "prof_phase_prefetch_ns",
    # cross-thread phase overlap (ingest: staging || compile run
    # concurrently, so per-phase walls may sum past the job wall —
    # this counter quantifies the legitimately-double-counted span)
    "prof_phase_overlap_ns",
    "prof_xfer_h2d_bytes", "prof_xfer_h2d_ns",
    "prof_xfer_d2h_bytes", "prof_xfer_d2h_ns",
    "prof_compile_cache_hits", "prof_compile_cache_misses",
    # the compile ledger (prof/compile.py; always on, fed by jax's own
    # events): what the job's OWN programs (named ompi_*) took to
    # trace, to lower, in XLA's compile and to load from the
    # persistent cache; how many reached the backend, asked the cache
    # and were answered by it; every other program's time and count
    "compile_trace_ns", "compile_lower_ns", "compile_backend_ns",
    "compile_cache_load_ns", "compile_programs",
    "compile_cache_requests", "compile_cache_hits",
    "compile_foreign_ns", "compile_foreign_programs",
    # monitoring plane per-context traffic (combined monitoring_msgs/
    # monitoring_bytes stay alongside; per-cell/per-link/per-expert
    # families are dynamically named — monitoring_tx_*_s<i>_d<j>_<ctx>,
    # monitoring_link_bytes_d<d>_r<a>_r<b>, monitoring_expert_tokens_e<k>)
    "monitoring_p2p_msgs", "monitoring_p2p_bytes",
    "monitoring_coll_msgs", "monitoring_coll_bytes",
    "monitoring_osc_msgs", "monitoring_osc_bytes",
    "monitoring_part_msgs", "monitoring_part_bytes",
    "monitoring_msgs", "monitoring_bytes",
    "monitoring_coll_launches", "monitoring_expert_tokens",
    "monitoring_link_imbalance_permille",
    # ingest/ plane (streaming H2D upload): uploads kicked off, units
    # + bytes landed, Parrived probes answered True, first steps
    # released before the tail finished (the pipeline win), gate wall,
    # units abandoned by cancel/error, compiles that provably ran
    # while an upload was in flight, per-stream put-queue depth hwm
    "ingest_uploads", "ingest_units", "ingest_bytes",
    "ingest_parrived", "ingest_early_starts", "ingest_gate_ns",
    "ingest_cancelled", "ingest_compile_overlaps", "ingest_inflight",
    # coll/pallas (hand-rolled ring collectives): kernel launches,
    # fused compute+comm kernel launches (ZeRO update / allgather-
    # matmul), staged fallthroughs to coll/xla, and bytes moved per
    # algorithm family (the switchpoint-tuning signal)
    "pallas_launches", "pallas_fused_launches", "pallas_fallthrough",
    "pallas_ring_bytes", "pallas_bidir_bytes", "pallas_linear_bytes",
    # coll/hier (two-level ICI x DCN collectives): hierarchical
    # launches, fused bucket launches riding the two-level lowering,
    # staged fallthroughs to the flat path, and per-level bytes — the
    # DCN figure is the one the smoke lane bounds at payload/ici_size;
    # hier_dcn_wire_bytes is what the slow wire ACTUALLY carried
    # (== nominal for exact launches, smaller under the compressed
    # bf16/fp8 coll_hier_dcn_dtype formats — the smoke lane bounds
    # the ratio at <=1/2 / <=1/4)
    "hier_launches", "hier_fused_launches", "hier_fallthrough",
    "hier_ici_bytes", "hier_dcn_bytes", "hier_dcn_wire_bytes",
    # zero/ error feedback (compressed-gradient residual carry): steps
    # that ran the quantize-and-carry cycle, and gradient payload
    # bytes quantized (Seide'14/Lin'18 — the residual keeps lossy
    # reduction convergence-neutral)
    "zero_ef_steps", "zero_ef_bytes",
    # ft/ failure plane: heartbeats emitted by the detector thread,
    # faults/revocations applied on the progress engine, and the
    # eventful-sweep wall (the hot no-news path is untimed — the
    # sweep runs on every progress tick)
    "ft_heartbeats", "ft_faults_observed", "ft_revokes_applied",
    "ft_sweep_ns",
    # elastic/ plane (shrink/regrow recovery): shrinks survived,
    # replacement ranks admitted, bytes allgathered for the in-memory
    # re-shard, recovery wall, checkpoint fallbacks taken vs
    # snapshots written, and deterministic kills the inject harness
    # fired (recorded in the doomed process)
    "elastic_shrinks", "elastic_hot_joins", "elastic_reshard_bytes",
    "elastic_recovery_ns", "elastic_fallback_restores",
    "elastic_checkpoints", "elastic_injected_kills",
    "elastic_injected_delays",
    # skew/ plane (cross-rank straggler attribution): completed
    # collectives recorded in the per-rank ring (+ overflow drops and
    # the ring's depth watermark), this rank's total exposed wait
    # (time spent blocked on later-arriving peers, folded in at
    # Finalize from the merged decomposition; per-op splits ride the
    # dynamic skew_op_wait_ns_<op> family), the worst single-
    # collective arrival skew seen, persistent stragglers named by
    # the verdict, and — at level 2 — the worst live lag the
    # watchdog's heartbeat sampling observed
    "skew_records", "skew_dropped", "skew_ring_depth",
    "skew_exposed_wait_ns", "skew_arrival_skew_ns",
    "skew_stragglers", "skew_live_lag_ns",
    # io/async_ckpt (crash-consistent overlapped checkpoints):
    # snapshots begun / epochs committed, chunk counts + shard bytes
    # + d2h/write walls, collective-write retries and the per-rank
    # synchronous degrades (never a lost snapshot), incremental
    # chunks skipped by digest-diff, restores served, epochs
    # abandoned by the newest-first fallback scan, digest mismatches
    # caught, and deterministic injected faults fired
    "ckpt_snapshots", "ckpt_commits", "ckpt_chunks", "ckpt_bytes",
    "ckpt_d2h_ns", "ckpt_write_ns", "ckpt_write_retries",
    "ckpt_fallback_sync", "ckpt_incremental_skipped",
    "ckpt_restores", "ckpt_restore_fallbacks",
    "ckpt_digest_mismatches", "ckpt_injected_failures",
    # serve/ plane (production-skew MoE serving): decode requests +
    # tokens dispatched, capacity-overflow outcomes per policy
    # (dropped / rerouted in-slice / shipped to a remote-slice replica
    # over DCN with the byte meter the budget cvar bounds); latency
    # histograms ride the trace plane's dynamic
    # trace_hist_serve_decode_* families. serve_dropped_tokens is also
    # fed by ops/moe.top1_routing's eager-mode metering (the
    # capacity-based router of the expert-parallel and serve paths; a
    # one-device MoE layer takes the drop-free sorted path and never
    # reaches it: moe_dropped_assignments below is its counter)
    "serve_requests", "serve_tokens", "serve_dropped_tokens",
    "serve_rerouted_tokens", "serve_dcn_overflow_tokens",
    "serve_dcn_overflow_bytes",
    # models/transformer.route_counts (a probe the host calls outside
    # any timed window): token-expert assignments the MoE layers made,
    # and those a capacity or an exchange lost — 0 on the sorted
    # one-device path, counted so that no later path drops in silence
    "moe_assignments", "moe_dropped_assignments",
    # fcoll aggregator writes retried after a short/partial result
    # (exhaustion raises MPIError(ERR_FILE) — satellites of the same
    # hardening pass)
    "fcoll_write_retries",
    # kvstore client: initial-connect retries burned before the store
    # answered (hot-joining ranks race store startup/recovery)
    "kvstore_connect_retries",
    # check/ plane (runtime MPI sanitizer): argument/signature
    # violations raised, leaked requests reported at Finalize,
    # cross-rank fingerprint exchanges performed at level 2
    "check_violations", "check_leaks", "check_sig_exchanges",
    "memchecker_violations",
    # check/ plane (static lint engine): files linted per run, files
    # served from the incremental cache, and CFG paths enumerated by
    # the path-sensitive lifecycle/divergence rules
    "check_lint_files", "check_lint_cached_files",
    "check_lint_cfg_paths",
    # every remaining literal recorded anywhere in the framework —
    # the check plane's unregistered-pvar lint rule enforces that
    # this tuple stays the single source of truth, so tools/info and
    # the OpenMetrics sampler export each name at 0 before first use
    "accel_p2p_send", "accel_p2p_recv",
    "adapt_ibcast", "adapt_ireduce",
    "coll_accelerator_staged", "coll_xla_device",
    "coll_xla_a2av_meta_cached", "coll_xla_alltoallv_fallback",
    "coll_xla_fns_size", "coll_xla_plans_size",
    "file_open", "file_read_bytes", "file_write_bytes",
    "han_allgather", "han_allreduce", "han_barrier", "han_bcast",
    "han_reduce",
    "inter_allgather", "inter_allreduce", "inter_barrier",
    "inter_bcast",
    "mem_hooks_released", "mpool_hits", "mpool_misses",
    "neighbor_allgather", "neighbor_allgatherv", "neighbor_alltoall",
    "neighbor_alltoallv",
    "osc_put", "osc_get", "osc_acc", "osc_fence",
    "osc_device_epoch_op", "osc_device_fallbacks",
    "osc_pallas_windows", "osc_pallas_put", "osc_pallas_get",
    "osc_pallas_acc", "osc_pallas_get_acc", "osc_pallas_fence",
    "osc_pallas_rounds", "osc_pallas_bytes", "osc_pallas_am_ops",
    "osc_pallas_fallthrough",
    "rcache_hits", "rcache_evictions",
    "rndv_frag", "rndv_sc",
    "shmem_alloc_bytes", "shmem_put", "shmem_get", "shmem_atomic",
    "smsc_bytes", "smsc_single_copies",
    "spawned_procs", "sync_injected_barriers",
    "telemetry_inflight",
    "tune_samples", "tune_dropped", "tune_table_errors",
    "tune_regressions", "tune_db_loads", "tune_db_saves",
    "tune_db_errors",
    "vprotocol_logged_sends", "vprotocol_resends",
)


def record(name: str, value: int = 1) -> None:
    """SPC_RECORD equivalent — add to a counter (inside `captured`, on
    that thread: to the capture's table instead)."""
    if _capturing:
        sink = getattr(_capture, "sink", None)
        if sink is not None:
            sink[name] = sink.get(name, 0) + value
            return
    with _lock:
        _counters[name] = _counters.get(name, 0) + value


class captured:
    """Context manager: what THIS thread records inside goes to the
    dict it yields and not to the counters. For code that runs once
    where the work it counts happens many times (a function jax traces
    once and calls per layer): its owner records the captured counts
    itself, once per call (models/remat.Recomputed)."""

    def __enter__(self) -> Dict[str, int]:
        global _capturing
        self._outer = getattr(_capture, "sink", None)
        _capture.sink = sink = {}
        with _lock:
            _capturing += 1
        return sink

    def __exit__(self, *exc):
        global _capturing
        _capture.sink = self._outer
        with _lock:
            _capturing -= 1
        return False


def record_hwm(name: str, value: int) -> None:
    """High-watermark pvar update."""
    with _lock:
        if value > _watermarks.get(name, 0):
            _watermarks[name] = value


class timer:
    """Context manager accumulating wall time into <name>_ns."""

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        record(self.name + "_ns", time.perf_counter_ns() - self.t0)
        return False


def read(name: str) -> int:
    with _lock:
        if name in _counters:
            return _counters[name]
        return _watermarks.get(name, 0)


def snapshot() -> Dict[str, int]:
    with _lock:
        out = dict(_counters)
        out.update({k + "_hwm": v for k, v in _watermarks.items()})
        return out


def reset() -> None:
    with _lock:
        _counters.clear()
        _watermarks.clear()


class session:
    """MPI_T-style pvar session: delta-reads counters from session start.

    Counter pvars read as deltas; watermark pvars read as the increase over
    the watermark at session start (MPI_T semantics: watermarks restart from
    the current value when a handle is bound).
    """

    def __init__(self) -> None:
        with _lock:
            self._base_counters = dict(_counters)
            self._base_hwm = dict(_watermarks)

    def read(self, name: str) -> int:
        with _lock:
            if name in _counters or name in self._base_counters:
                return _counters.get(name, 0) - \
                    self._base_counters.get(name, 0)
            return max(0, _watermarks.get(name, 0) -
                       self._base_hwm.get(name, 0))

    def snapshot(self) -> Dict[str, int]:
        cur = globals()["snapshot"]()
        base = dict(self._base_counters)
        base.update({k + "_hwm": v for k, v in self._base_hwm.items()})
        return {k: v - base.get(k, 0) for k, v in cur.items()}
