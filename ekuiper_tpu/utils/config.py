"""Engine configuration — analogue of eKuiper's etc/kuiper.yaml → model.KuiperConf
(reference: pkg/model/conf.go:28, internal/conf/env_manager.go).

Sections mirror the reference: basic / rule / sink / source / store / portable.
Values can be overridden by environment variables of the form
EKUIPER_TPU__<SECTION>__<KEY> (double underscore separators), mirroring the
reference's env overlay scheme.
"""
from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

ENV_PREFIX = "EKUIPER_TPU__"


@dataclass
class RuleOptionConfig:
    """Default per-rule options (reference: internal/pkg/def/rule.go:27-49)."""

    debug: bool = False
    log_filename: str = ""
    is_event_time: bool = False
    late_tolerance_ms: int = 1000
    concurrency: int = 1
    buffer_length: int = 1024
    send_error: bool = True
    qos: int = 0  # 0 AtMostOnce, 1 AtLeastOnce, 2 ExactlyOnce
    checkpoint_interval_ms: int = 300_000
    restart_attempts: int = 0  # 0 = no restart; -1 = infinite
    restart_delay_ms: int = 1000
    restart_multiplier: float = 2.0
    restart_max_delay_ms: int = 30_000
    restart_jitter_factor: float = 0.1
    disable_buffer_full_discard: bool = False
    # TPU execution options
    micro_batch_rows: int = 4096
    micro_batch_linger_ms: int = 10
    # sharded ingest pipeline (runtime/ingest.py): decode_pool_size worker
    # threads decode drained payload runs off the connector thread, handing
    # ColumnBatches to the fused node through a bounded ring so decode of
    # batch k+1 overlaps the upload+fold of batch k. Default 0 = decode
    # inline on the ingest thread: emission then happens synchronously
    # inside ingest/flush, which rules driven by the mockable clock
    # (timex) depend on. Byte-fed production pipelines should set 2-4
    # (the full-pipe bench runs with 3).
    decode_pool_size: int = 0
    # native parse shards per decode call (jsoncol.cpp GIL-free pass);
    # 0 = auto (decode_pool_size when the pool is on, else 1)
    decode_shards: int = 0
    # decoded-batch ring depth: in-flight decodes before submit blocks
    # (backpressure toward the connector)
    ingest_ring_depth: int = 2
    # pipelined upload stage (pool-on only): decode-pool workers key-slot-
    # encode each batch (native C table when built) and pre-pad +
    # device_put its kernel inputs, so H2D of batch k+1 overlaps the fold
    # of batch k and the fused worker's upload stage collapses to share-
    # cache hits. Off = pool decodes only, fused node preps inline.
    ingest_prep_upload: bool = True
    # HBM budget for sliding-window device state beyond the panes: the
    # DABA ring partials (ops/slidingring.py — allocation refused past the
    # cap, rule falls back to refold) and the refold impl's _dev_ring
    # fold-input cache (FIFO-evicted past the cap, refolds fall back to
    # exact host uploads)
    sliding_dev_ring_mb: int = 256
    # sliding trigger emission: "daba" = constant-time two-stack rings
    # (ops/slidingring.py, default); "refold" = legacy pane-merge +
    # edge-refold path (parity baseline / escape hatch)
    sliding_impl: str = "daba"
    # stream-stream joins: "device" = banded-gather ring kernel
    # (ops/joinring.py) when the ON clause lowers, with per-window host
    # fallback; "host" = always the nested-loop reference operator
    join_impl: str = "device"
    # analytic/window functions: "device" = lag on the segscan shift
    # kernel + rank/dense_rank through the segscan sort kernel
    # (ops/segscan.py); "host" = per-row evaluator state machines
    analytic_impl: str = "device"
    key_slots: int = 16384  # group-by hash-slot table size per rule
    # tiered key state (ops/tierstore.py, docs/TIERED_STATE.md): "auto"
    # enables the HBM-resident hot set + host cold tier when
    # KUIPER_HBM_BUDGET_MB is set and too tight for the rule's capacity
    # ladder; "on" forces it (budget or tierHotMb required), "off"
    # disables. Cold keys' per-pane partials spill to a pinned host
    # arena and their device slots recycle through the key table.
    tier_store: str = "auto"
    # explicit hot-tier HBM allowance (MB); 0 = derive from
    # KUIPER_HBM_BUDGET_MB
    tier_hot_mb: int = 0
    # placement-policy cadence; 0 = derive from the window geometry
    tier_scan_ms: int = 0
    use_device_kernel: bool = True  # fuse window+agg into a jitted kernel when possible
    # pre-issue the window finalize this long before the boundary so the
    # device round trip overlaps the stream (ops/prefinalize.py); 0 disables
    prefinalize_lead_ms: int = 250
    # fused window results stay columnar (ColumnBatch) end-to-end; sinks
    # convert to per-message dicts at the edge
    emit_columnar: bool = True
    # one shared ingest+decode pipeline per stream config across qos=0 rules
    # (reference subtopo_pool); checkpointed rules always get a private source
    share_source: bool = True
    # cost-based cross-rule window-aggregate sharing (planner/sharing.py):
    # correlated rules over one stream fold once into a shared pane store
    # and combine panes per window. qos=0 + share_source only; the planner
    # falls back to a private fold (logged) when the rewrite doesn't apply
    # or its cost model says it won't pay.
    shared_fold: bool = True
    # planOptimizeStrategy analogue (reference: internal/pkg/def/rule.go:55-66);
    # {"mesh": {"rows": R, "keys": K}} runs the fused kernel sharded over an
    # R x K device mesh (parallel/sharded.py)
    plan_optimize_strategy: Dict[str, Any] = field(default_factory=dict)


@dataclass
class StoreConfig:
    type: str = "sqlite"  # sqlite | memory
    path: str = "data"


@dataclass
class BasicConfig:
    log_level: str = "info"
    rest_port: int = 9081
    rest_ip: str = "0.0.0.0"
    prometheus: bool = False
    prometheus_port: int = 20499
    ignore_case: bool = False
    time_zone: str = "UTC"
    # REST JWT auth (reference internal/pkg/jwt — uses registered RSA keys;
    # here an HS256 shared secret, documented divergence). Off by default.
    authentication: bool = False
    jwt_secret: str = ""
    # per-rule log files under <store.path>/logs (rule logToDisk analogue)
    rule_log_enabled: bool = False


@dataclass
class SinkConfig:
    mem_cache_threshold: int = 1024
    max_disk_cache: int = 1024000
    buffer_page_size: int = 256
    resend_interval_ms: int = 0
    clean_cache_at_stop: bool = False


@dataclass
class SourceConfig:
    http_server_ip: str = "0.0.0.0"
    http_server_port: int = 10081


@dataclass
class PortableConfig:
    python_bin: str = "python"
    init_timeout_ms: int = 5000


@dataclass
class ClusterConfig:
    """Multi-host mesh participation (jax.distributed). When enabled, every
    host runs the engine with the same config; meshes built from
    jax.devices() then span all hosts, kernel collectives ride ICI inside a
    pod slice and DCN across slices. See docs/DISTRIBUTED.md for the
    execution model and its constraints."""

    enabled: bool = False
    coordinator_address: str = ""  # host:port of process 0
    num_processes: int = 1
    process_id: int = 0


@dataclass
class OpenTelemetryConfig:
    """Remote OTLP span export (reference pkg/tracer/manager.go:28-45 —
    otlptracehttp with WithInsecure). Off by default: zero egress unless
    explicitly pointed at a collector."""

    enable_remote_collector: bool = False
    remote_endpoint: str = "localhost:4318"
    service_name: str = "ekuiper_tpu"  # resource attribute on exported spans
    batch_max_spans: int = 512
    batch_interval_ms: int = 2000


@dataclass
class Config:
    basic: BasicConfig = field(default_factory=BasicConfig)
    rule: RuleOptionConfig = field(default_factory=RuleOptionConfig)
    store: StoreConfig = field(default_factory=StoreConfig)
    sink: SinkConfig = field(default_factory=SinkConfig)
    source: SourceConfig = field(default_factory=SourceConfig)
    portable: PortableConfig = field(default_factory=PortableConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    open_telemetry: OpenTelemetryConfig = field(
        default_factory=OpenTelemetryConfig)
    data_dir: str = "data"

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _coerce(value: str, target_type: type) -> Any:
    if target_type is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if target_type is int:
        return int(value)
    if target_type is float:
        return float(value)
    return value


def _apply_env(cfg: Config) -> None:
    for key, value in os.environ.items():
        if not key.startswith(ENV_PREFIX):
            continue
        parts = key[len(ENV_PREFIX):].lower().split("__")
        if len(parts) != 2:
            continue
        section, name = parts
        sec = getattr(cfg, section, None)
        if sec is None or not hasattr(sec, name):
            continue
        current = getattr(sec, name)
        setattr(sec, name, _coerce(value, type(current)))


def load_config(path: Optional[str] = None) -> Config:
    """Load config from a JSON file (if given/exists) then apply env overrides."""
    cfg = Config()
    if path and os.path.exists(path):
        with open(path) as f:
            raw = json.load(f)
        for section, values in raw.items():
            sec = getattr(cfg, section, None)
            if sec is None or not dataclasses.is_dataclass(sec):
                continue
            for k, v in values.items():
                if hasattr(sec, k):
                    setattr(sec, k, v)
    _apply_env(cfg)
    return cfg


_global: Optional[Config] = None


def apply_config_overlay(store) -> None:
    """Re-apply runtime PATCH /configs overlays persisted in the KV store
    (server/rest.py patch_configs) so patches survive restarts."""
    cfg = get_config()
    overlay = store.kv("config_overlay")
    for key in overlay.keys():
        val, ok = overlay.get_ok(key)
        if ok and hasattr(cfg.basic, key):
            setattr(cfg.basic, key, val)


def get_config() -> Config:
    global _global
    if _global is None:
        _global = load_config(os.environ.get("EKUIPER_TPU_CONFIG"))
    return _global


def set_config(cfg: Config) -> None:
    global _global
    _global = cfg
