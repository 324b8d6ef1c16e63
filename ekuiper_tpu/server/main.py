"""Server bootstrap — analogue of eKuiper's StartUp sequence
(internal/server/server.go:139-330): config → store → keyed state →
processors → rule recovery → REST server → run until signalled.

Run: python -m ekuiper_tpu.server.main [--config conf.json]
"""
from __future__ import annotations

import argparse
import logging
import os
import signal
import threading

from ..store import kv
from ..utils.config import get_config, load_config, set_config
from ..utils.infra import logger
from .rest import RestApi, serve


def start_up(config_path: str | None = None, block: bool = True):
    cfg = load_config(config_path)
    set_config(cfg)
    logging.basicConfig(
        level=getattr(logging, cfg.basic.log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    # before anything touches jax: where compiled programs persist
    from ..utils import jaxcache

    logger.info("jax compilation cache at %s", jaxcache.setup())
    if cfg.cluster.enabled:
        # validate BEFORE the (blocking) init — a half-filled cluster
        # section must fail loudly, not hang a silent boot
        cc = cfg.cluster
        if not cc.coordinator_address:
            raise ValueError("cluster.coordinator_address is required")
        if not (0 <= cc.process_id < cc.num_processes):
            raise ValueError(
                f"cluster.process_id {cc.process_id} out of range for "
                f"{cc.num_processes} processes")
        # must run before anything touches jax: after this, jax.devices()
        # spans every participating host and meshes shard across them
        # (collectives ride ICI within a slice, DCN across slices)
        import jax

        logging.getLogger("ekuiper_tpu").info(
            "joining cluster %s as process %d/%d",
            cc.coordinator_address, cc.process_id, cc.num_processes)
        jax.distributed.initialize(
            coordinator_address=cc.coordinator_address,
            num_processes=cc.num_processes,
            process_id=cc.process_id,
        )
    store = kv.setup(cfg.store.type, cfg.store.path)
    from ..utils.config import apply_config_overlay

    apply_config_overlay(store)  # PATCH /configs overlays survive restarts
    if cfg.basic.rule_log_enabled:
        from ..utils import rulelog

        rulelog.install(os.path.join(cfg.store.path, "logs"))
    # portable plugin manager (restores installed plugins + binds symbols,
    # reference: server.go:218-226 binder init)
    from ..plugin.manager import PortableManager
    from ..plugin.script import ScriptManager

    from ..schema.registry import SchemaRegistry

    PortableManager.set_global(PortableManager(store))
    ScriptManager.set_global(ScriptManager(store))
    SchemaRegistry.set_global(SchemaRegistry(
        store, etc_dir=f"{cfg.store.path}/schemas"))
    from ..services.manager import ServiceManager

    ServiceManager.set_global(ServiceManager(store))
    # remote OTLP span tee (off by default; pkg/tracer/manager.go:28-45)
    from ..observability.otlp import from_config as otlp_from_config
    from ..observability.tracer import Tracer

    exporter = otlp_from_config(cfg)
    if exporter is not None:
        Tracer.global_instance().set_exporter(exporter)
        logger.info("OTLP span export -> %s", exporter.url)
    api = RestApi(store)
    api.rules.recover()
    server = serve(api, cfg.basic.rest_ip, cfg.basic.rest_port)

    stop_event = threading.Event()

    def shutdown(*_args) -> None:
        logger.info("shutting down")
        from ..observability import health
        from ..runtime import control

        control.reset()  # stop the QoS controller's recurring timer
        health.reset()  # stop the evaluator's recurring timer
        api.rules.stop_all()
        PortableManager.global_instance().kill_all()  # server.go:329 KillAll
        if exporter is not None:
            Tracer.global_instance().set_exporter(None)  # closes + final flush
        server.shutdown()
        stop_event.set()

    if block:
        signal.signal(signal.SIGINT, shutdown)
        signal.signal(signal.SIGTERM, shutdown)
        stop_event.wait()
        return None
    return api, server


def main() -> None:
    ap = argparse.ArgumentParser(description="ekuiper_tpu server")
    ap.add_argument("--config", default=None, help="config json path")
    args = ap.parse_args()
    start_up(args.config)


if __name__ == "__main__":
    main()
