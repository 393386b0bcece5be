"""The metric catalog — the single machine-readable registry of every
metric this codebase can emit.

Three consumers keep each other honest through it (the CI drift gate in
tests/test_observability4.py):

- the SOURCE: every ``Counter/Gauge/Histogram("name", ...)`` literal in
  the package (extracted by `source_metrics()`, an AST scan) must have
  a catalog entry, and vice versa;
- the DOCS: every catalog name must appear in OBSERVABILITY.md's
  catalog table, and every metric named there must exist here;
- the DASHBOARD: ``python -m ray_tpu.devtools.grafana [-o PATH]``
  generates it from this catalog on request (one panel per metric,
  typed expressions); no generated copy is committed.

Adding a metric therefore means: construct it, add its row here, add
its OBSERVABILITY.md row. Forgetting any of the three fails the gate.
"""

from __future__ import annotations

import ast
import os

# (name, type, where, what) — grouped/ordered like OBSERVABILITY.md
CATALOG: list[dict] = [
    # train
    {"name": "train_step_seconds", "type": "histogram",
     "where": "ray_tpu/train/spmd.py",
     "what": "host-side train-step dispatch time"},
    {"name": "train_compile_misses_total", "type": "counter",
     "where": "ray_tpu/train/spmd.py",
     "what": "train steps that triggered an XLA compile"},
    {"name": "train_compile_seconds", "type": "histogram",
     "where": "ray_tpu/train/spmd.py",
     "what": "XLA compile time for the train step"},
    {"name": "train_step_phase_seconds", "type": "histogram",
     "where": "ray_tpu/train/spmd.py",
     "what": "per-step waterfall phases incl. collective.<op> buckets "
             "(attribution runs only)"},
    {"name": "train_optimizer_state_bytes", "type": "gauge",
     "where": "ray_tpu/train/spmd.py",
     "what": "per-chip optimizer-state bytes, by layout "
             "(replicated|zero1) — the ZeRO-1 memory win"},
    {"name": "train_grad_state_bytes", "type": "gauge",
     "where": "ray_tpu/train/spmd.py",
     "what": "per-chip resident grad-accum bytes, by layout "
             "(replicated|zero2) — the ZeRO-2 memory win"},
    {"name": "train_param_state_bytes", "type": "gauge",
     "where": "ray_tpu/train/spmd.py",
     "what": "per-chip resident param bytes, by layout "
             "(replicated|zero3) — the ZeRO-3 memory win"},
    {"name": "train_zero_gather_share", "type": "gauge",
     "where": "ray_tpu/train/spmd.py",
     "what": "all-gather share of train step time at zero_stage >= 3 "
             "(attribution runs) — the JIT param-gather cost"},
    {"name": "train_pipeline_bubble_ratio", "type": "gauge",
     "where": "ray_tpu/train/pipeline_strategy.py",
     "what": "measured 1F1B bubble fraction of the last pipeline step"},
    {"name": "train_pipeline_virtual_stages", "type": "gauge",
     "where": "ray_tpu/train/pipeline_strategy.py",
     "what": "virtual stages (stages x repeats) of the running "
             "pipeline — > stages means interleaved 1F1B is active"},
    {"name": "train_microbatches_total", "type": "counter",
     "where": "ray_tpu/train/pipeline_strategy.py",
     "what": "microbatches executed by the pipeline train strategy"},
    # collectives
    {"name": "collective_seconds", "type": "histogram",
     "where": "ray_tpu/util/collective.py",
     "what": "host-side collective wall time (offer -> ready)"},
    # object plane
    {"name": "object_store_pull_bytes_total", "type": "counter",
     "where": "ray_tpu/core/nodelet.py",
     "what": "inbound node-to-node object transfer bytes"},
    {"name": "object_store_pull_seconds", "type": "histogram",
     "where": "ray_tpu/core/nodelet.py",
     "what": "inbound node-to-node object transfer latency"},
    {"name": "object_store_push_bytes_total", "type": "counter",
     "where": "ray_tpu/core/nodelet.py",
     "what": "bytes served to other nodes"},
    {"name": "object_store_bytes_allocated", "type": "gauge",
     "where": "ray_tpu/core/nodelet.py",
     "what": "store occupancy in bytes (refreshed at scrape)"},
    {"name": "object_store_num_objects", "type": "gauge",
     "where": "ray_tpu/core/nodelet.py", "what": "objects resident"},
    {"name": "object_store_evictions", "type": "gauge",
     "where": "ray_tpu/core/nodelet.py", "what": "objects evicted"},
    {"name": "object_store_created_objects_total", "type": "counter",
     "where": "ray_tpu/core/object_store.py",
     "what": "per-process store writes (count)"},
    {"name": "object_store_created_bytes_total", "type": "counter",
     "where": "ray_tpu/core/object_store.py",
     "what": "per-process store writes (bytes)"},
    {"name": "object_store_stranded_bytes", "type": "gauge",
     "where": "ray_tpu/core/cluster_runtime.py",
     "what": "bytes held by owned refs past the stranded-age threshold "
             "with no consumer progress (refreshed at scrape)"},
    # serve.llm engine
    {"name": "serve_llm_tokens_generated_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py", "what": "tokens generated"},
    {"name": "serve_llm_requests_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "requests finished, by outcome"},
    {"name": "serve_llm_preemptions_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "sequences preempted on cache exhaustion"},
    {"name": "serve_llm_queue_depth", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py", "what": "waiting requests"},
    {"name": "serve_llm_running", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "sequences in the decode set"},
    {"name": "serve_llm_cache_utilization", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "KV pool pages in use / usable"},
    {"name": "serve_llm_ttft_ms", "type": "histogram",
     "where": "ray_tpu/serve/llm/engine.py", "what": "time to first token"},
    {"name": "serve_llm_itl_ms", "type": "histogram",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "gap between two streamed tokens of one request, by what "
             "the loop did in it (cause)"},
    {"name": "serve_llm_step_ms", "type": "histogram",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "engine step latency, by kind"},
    {"name": "serve_llm_prefix_cache_hits_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "KV pages served from the prefix cache at admission"},
    {"name": "serve_llm_prefix_cache_misses_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "KV pages prefilled at admission"},
    {"name": "serve_llm_prefix_cache_evictions_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "cached refcount-0 pages evicted for reuse"},
    {"name": "serve_llm_prefix_cached_blocks", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "refcount-0 pages retained for prefix reuse"},
    {"name": "serve_llm_prefill_chunks_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py", "what": "prefill chunks run"},
    {"name": "serve_llm_compile_misses_total", "type": "counter",
     "where": "ray_tpu/serve/llm/runner.py",
     "what": "prefill/decode calls that triggered an XLA compile"},
    {"name": "serve_llm_compile_seconds", "type": "histogram",
     "where": "ray_tpu/serve/llm/runner.py",
     "what": "XLA compile time per LLM program"},
    {"name": "serve_llm_weight_swaps_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "weight hot-swaps installed at a step boundary"},
    {"name": "serve_llm_spec_proposed_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "draft tokens proposed to the speculative verify program"},
    {"name": "serve_llm_spec_accepted_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "draft tokens accepted by the verify program"},
    {"name": "serve_llm_spec_rejected_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "draft tokens rejected by the verify program (the "
             "spec-accept-collapse rule's miss side)"},
    {"name": "serve_llm_spec_accept_ratio", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "cumulative accepted / proposed draft tokens"},
    {"name": "serve_llm_verify_step_ms", "type": "histogram",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "speculative verify step latency (K+1-wide program)"},
    {"name": "serve_llm_d2h_bytes_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "bytes of device results (tokens, logits) the engine's "
             "steps fetched to the host, by step kind"},
    {"name": "serve_llm_ctx_slots_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "slots of cached context by kind of program: read as "
             "launched, valid, and what reading every row to "
             "max_model_len would be; of a latent kind also scored "
             "(indexer keys read) and selected (slots attended after "
             "the indexer's choice)"},
    {"name": "serve_llm_steps_launched_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "step programs enqueued, by step kind and by whether the "
             "step before them was still unread (ahead=1) or not"},
    {"name": "serve_llm_step_drains_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "steps read with none launched behind them, by what kept "
             "the next one from being planned"},
    {"name": "serve_llm_discarded_tokens_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "sampled ids dropped at commit: their lane had ended "
             "while the program ran"},
    {"name": "serve_llm_weight_bytes", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "bytes of the resident parameter tree, each leaf in the "
             "dtype the serve programs consume it in"},
    {"name": "serve_llm_weight_cast_leaves", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "leaves the last weight install converted to their "
             "resident dtype (0: the tree arrived as it is held)"},
    # routed experts (a model with none reports none)
    {"name": "serve_llm_moe_pairs_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "(token, expert) pairs the routed-expert layers computed, "
             "padded rows included, by step kind"},
    {"name": "serve_llm_moe_experts_touched_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "experts that received at least one pair, summed over "
             "programs and layers, by step kind"},
    {"name": "serve_llm_moe_layer_calls_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "routed-expert layers run (programs x layers), by step "
             "kind"},
    {"name": "serve_llm_moe_load_imbalance", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "pairs of the most loaded expert over the mean expert's, "
             "cumulative, by step kind (1.0: an even router)"},
    # recurrent state (nemotron_h, lfm2, granite_hybrid; a family
    # without it writes 0 and nothing more)
    {"name": "serve_llm_state_bytes", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "bytes of recurrent state held for the lane slots, all "
             "layers and parts (0: the family has none)"},
    {"name": "serve_llm_state_resets_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "lane slots started from zero by a program that ran a "
             "sequence's first rows (admissions, recomputes included)"},
    {"name": "serve_llm_state_carried_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "prefill programs that started from the recurrent state an "
             "earlier chunk of their sequence left in the lane's slot"},
    {"name": "serve_llm_state_decode_lanes_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "lane slots whose recurrent state a decode program moved "
             "one step on (the slots its lanes owned), summed over steps"},
    # KV pages by kind of layer (`kind`: "full" for most families, "full"
    # and "window" for one that mixes full and window attention)
    {"name": "serve_llm_kv_pages_used", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "pages of a kind's pool held by sequences"},
    {"name": "serve_llm_kv_pages_free", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "pages of a kind's pool that can be allocated"},
    {"name": "serve_llm_kv_largest_table", "type": "gauge",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "most pages of a kind one sequence has held at once"},
    {"name": "serve_llm_kv_released_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "pages given back behind the window while their sequence "
             "ran (a window kind only)"},
    {"name": "serve_llm_kv_prefix_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "admissions by what became of their prefix lookup: taken, "
             "or declined (a family with a window kind looks none up)"},
    {"name": "serve_llm_kv_rows_written_total", "type": "counter",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "valid rows of K (and as many of V) stored in a kind's "
             "pools, by path: paged (a prompt's or a chunk's program, a "
             "page at a time) or rowwise (decode, verify, a bucket that "
             "is not whole pages)"},
    # jax's own account of its compiles (every process that compiles)
    {"name": "jax_compile_seconds_total", "type": "counter",
     "where": "ray_tpu/util/tracing.py",
     "what": "seconds jax spent per compile stage: trace, lower, "
             "backend_compile (XLA or the cache load), cache_retrieval"},
    {"name": "jax_compile_cache_total", "type": "counter",
     "where": "ray_tpu/util/tracing.py",
     "what": "persistent compile cache lookups that found (hits) or "
             "wrote (misses) an entry"},
    # serve SLO attribution (the per-request waterfall's metric face)
    {"name": "serve_slo_ttft_ms", "type": "histogram",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "TTFT decomposed: phase=queue|prefill|total"},
    {"name": "serve_slo_tpot_ms", "type": "histogram",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "decode seconds per output token after the first"},
    # serve proxy
    {"name": "serve_num_http_requests", "type": "counter",
     "where": "ray_tpu/serve/api.py", "what": "HTTP ingress, by status"},
    {"name": "serve_http_request_latency_ms", "type": "histogram",
     "where": "ray_tpu/serve/api.py", "what": "HTTP ingress latency"},
    # serve self-healing
    {"name": "serve_replica_health_checks_total", "type": "counter",
     "where": "ray_tpu/serve/api.py",
     "what": "controller health probes, by result (ok|miss|dead)"},
    {"name": "serve_replica_restarts_total", "type": "counter",
     "where": "ray_tpu/serve/api.py",
     "what": "replica replacements started by the self-healing loop"},
    {"name": "serve_replicas_healthy", "type": "gauge",
     "where": "ray_tpu/serve/api.py",
     "what": "replicas passing their latest health probe round"},
    {"name": "serve_request_failovers_total", "type": "counter",
     "where": "ray_tpu/serve/api.py",
     "what": "requests re-submitted after replica death (unary "
             "retries + mid-stream resumes)"},
    # RL flywheel
    {"name": "rl_rollout_tokens_total", "type": "counter",
     "where": "ray_tpu/rllib/llm/rollout.py",
     "what": "tokens generated by RL rollouts"},
    {"name": "rl_reward_mean", "type": "gauge",
     "where": "ray_tpu/rllib/llm/rollout.py",
     "what": "mean reward of the latest rollout batch"},
    {"name": "rl_traj_staleness", "type": "histogram",
     "where": "ray_tpu/rllib/llm/learner.py",
     "what": "weight-version lag of offered trajectories"},
    {"name": "rl_traj_dropped_total", "type": "counter",
     "where": "ray_tpu/rllib/llm/learner.py",
     "what": "trajectories refused by the staleness guard"},
    {"name": "rl_weight_swap_seconds", "type": "histogram",
     "where": "ray_tpu/serve/llm/engine.py",
     "what": "drain-free weight hot-swap wall time"},
    # core fast path (coalesced submission + compiled DAGs)
    {"name": "rpc_oneway_batch_size", "type": "histogram",
     "where": "ray_tpu/core/rpc.py",
     "what": "messages coalesced per flushed batch frame"},
    {"name": "core_submit_coalesced_total", "type": "counter",
     "where": "ray_tpu/core/cluster_runtime.py",
     "what": "submissions/returns that rode a coalesced frame, by kind"},
    {"name": "dag_executions_total", "type": "counter",
     "where": "ray_tpu/dag/__init__.py",
     "what": "compiled-DAG executions, by path (compiled|eager_fallback)"},
    # task flight recorder (lifecycle ledger)
    {"name": "task_queue_wait_seconds", "type": "histogram",
     "where": "ray_tpu/core/nodelet.py",
     "what": "time tasks spend in a nodelet's dispatch queue (enqueue "
             "to dispatch) — the task-queue-stall rule's input"},
    {"name": "task_ledger_events_total", "type": "counter",
     "where": "ray_tpu/core/task_ledger.py",
     "what": "lifecycle transitions ingested by the head task ledger"},
    {"name": "task_ledger_dropped_total", "type": "counter",
     "where": "ray_tpu/core/task_ledger.py",
     "what": "lifecycle transitions dropped by the per-record "
             "transition cap — drops counted, never silent"},
    # profiler plane
    {"name": "core_task_cpu_seconds_total", "type": "counter",
     "where": "ray_tpu/core/cluster_runtime.py",
     "what": "CPU seconds consumed executing tasks and actor methods, "
             "by kind (fed by the worker exec loop)"},
    {"name": "profile_captures_total", "type": "counter",
     "where": "ray_tpu/util/profiler.py",
     "what": "sampling-profiler capture windows completed"},
    {"name": "profile_samples_total", "type": "counter",
     "where": "ray_tpu/util/profiler.py",
     "what": "stack sample ticks taken across capture windows"},
    {"name": "profile_stacks_dropped_total", "type": "counter",
     "where": "ray_tpu/util/profiler.py",
     "what": "thread-samples rejected by the unique-stack cap"},
    # log plane
    {"name": "log_records_total", "type": "counter",
     "where": "ray_tpu/utils/logging.py",
     "what": "structured log records emitted, by level (the "
             "error-rate-spike rule's input)"},
    {"name": "log_bytes_total", "type": "counter",
     "where": "ray_tpu/utils/logging.py",
     "what": "structured JSONL log bytes written"},
    {"name": "log_records_dropped_total", "type": "counter",
     "where": "ray_tpu/utils/logging.py",
     "what": "log records lost to serialization/disk failure "
             "(drops counted, never silent)"},
    # span plane
    {"name": "spans_sampled_total", "type": "counter",
     "where": "ray_tpu/utils/events.py",
     "what": "spans admitted into the local buffer, by category"},
    {"name": "spans_dropped_total", "type": "counter",
     "where": "ray_tpu/utils/events.py",
     "what": "spans rejected (sampling policy or full buffer)"},
    # watchtower (alerting plane)
    {"name": "watchtower_alerts_firing", "type": "gauge",
     "where": "ray_tpu/util/watchtower.py",
     "what": "alerts currently firing, by severity"},
    {"name": "watchtower_alerts_total", "type": "counter",
     "where": "ray_tpu/util/watchtower.py",
     "what": "pending->firing transitions, by rule"},
    {"name": "watchtower_samples_total", "type": "counter",
     "where": "ray_tpu/util/watchtower.py",
     "what": "metric-history sample ticks completed"},
    {"name": "watchtower_series", "type": "gauge",
     "where": "ray_tpu/util/watchtower.py",
     "what": "metric-history series retained"},
    {"name": "watchtower_series_dropped_total", "type": "counter",
     "where": "ray_tpu/util/watchtower.py",
     "what": "new series rejected by the history series cap"},
    {"name": "watchtower_autodumps_total", "type": "counter",
     "where": "ray_tpu/util/watchtower.py",
     "what": "debug dumps auto-triggered by critical alerts"},
]


def catalog_names() -> set[str]:
    return {m["name"] for m in CATALOG}


def source_metrics(package_root: str | None = None) -> dict[str, str]:
    """{metric name: type} for every Counter/Gauge/Histogram construction
    with a literal name in the package source — the 'registered at
    runtime' side of the drift gate, extracted statically so the gate
    covers paths no test instantiates."""
    if package_root is None:
        package_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
    found: dict[str, str] = {}
    for dirpath, dirnames, filenames in os.walk(package_root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path, encoding="utf-8") as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError):
                continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                f_ = node.func
                ctor = (f_.id if isinstance(f_, ast.Name)
                        else f_.attr if isinstance(f_, ast.Attribute)
                        else None)
                if ctor in ("Counter", "Gauge", "Histogram") \
                        and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    found[node.args[0].value] = ctor.lower()
    return found
