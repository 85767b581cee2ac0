"""Hub / single-service gRPC server entry point (console script ``lumen-tpu``).

Startup sequence mirrors the reference hub runner
(``src/lumen/server.py:188-385``): load+validate config -> ensure model
artifacts -> instantiate services from their configured ``registry_class``
dotted paths -> bind gRPC (with OS-assigned port fallback) -> advertise
over mDNS -> serve until SIGINT/SIGTERM.

Unlike the reference, ``single`` and ``hub`` modes share this one entry
point (the reference duplicates a per-package server runner in each of the
four model packages); single mode is simply a hub with one service.

A third mode exists beyond the reference: with ``LUMEN_FED_PEERS`` set
and no enabled services, this server boots as a federation **front
tier** — it owns no models and consistent-hash-routes every request over
its peer servers on the unchanged protocol (docs/ARCHITECTURE.md "Fleet
federation"). With services AND peers it is a *peer-aware backend*:
local result-cache misses consult the ring owner's cache before
computing.

Unlike the reference (and this repo's seed), startup failure of ONE
service no longer aborts the hub: a failed download or ``from_config``
boots that service as a :class:`~lumen_tpu.serving.resilience.DegradedService`
(tasks answer UNAVAILABLE with a recovery hint) while a background
:class:`~lumen_tpu.serving.resilience.RecoveryManager` retries the load
with exponential backoff and hot-swaps the real service in on success.
``LUMEN_STRICT_BOOT=1`` restores the old abort-on-any-failure behavior.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import sys
import threading
from concurrent import futures

import grpc

from ..core.config import LumenConfig, load_config
from ..core.downloader import Downloader
from ..core.exceptions import DownloadError
from ..utils.logger import setup_logging
from .base_service import BaseService
from .breaker import CircuitBreaker, breaker_failures
from .loader import resolve
from .mdns import MdnsAdvertiser
from .resilience import DegradedService, RecoveryManager, expected_tasks_for
from .router import (
    FederationRouter,
    HubRouter,
    grpc_pool_size,
    handler_pool,
    install_handler_pool,
)

logger = logging.getLogger(__name__)

GRPC_OPTIONS = [
    ("grpc.max_send_message_length", 64 * 1024 * 1024),
    ("grpc.max_receive_message_length", 64 * 1024 * 1024),
]

DRAIN_ENV = "LUMEN_DRAIN_S"
DRAIN_ANNOUNCE_ENV = "LUMEN_DRAIN_ANNOUNCE_S"

#: a capacity probe older than this means nobody is watching anymore (a
#: front polls every LUMEN_FED_POLL_S, default 2s) — don't hold shutdown
#: for a departed observer.
_DRAIN_WATCHER_STALE_S = 15.0


def grpc_workers() -> int:
    """gRPC handler threads of the server this process runs: ten, plus one
    for every decode slot of the caption engines its services hold
    (:func:`~lumen_tpu.serving.router.grpc_pool_size`), unless
    ``LUMEN_GRPC_WORKERS`` sets it. A federation front tier holds no
    engine and wants more — each forwarded stream parks one handler thread
    on a peer RPC, so the front's concurrency ceiling is exactly this
    number."""
    return handler_pool().size


def decode_slots(services: dict[str, BaseService]) -> int:
    """Decode slots over every caption engine the services report."""
    return sum(svc.decode_slots() for svc in services.values())


def drain_budget_s() -> float:
    """``LUMEN_DRAIN_S``: seconds a SIGTERM/SIGINT shutdown spends
    draining (default 10) — new RPCs answer UNAVAILABLE with a retry-after
    hint while queued and in-flight work completes; stragglers past the
    budget are aborted, then the process exits. ``0`` restores the
    immediate-stop behavior."""
    from ..utils.env import env_float

    return env_float(DRAIN_ENV, 10.0, minimum=0.0)


def drain_announce_s() -> float:
    """``LUMEN_DRAIN_ANNOUNCE_S``: max extra seconds an idle drain holds
    the server open so capacity gossip can announce the draining flag to
    a watching front (default 5; ``0`` disables the hold). Only applies
    when a Health probe carried this host's capacity report recently —
    a standalone or ungossiped server shuts down exactly as before. The
    hold ends early the moment a probe is served with the flag set, plus
    a short margin for the front's hot-key handoff fetches to arrive;
    always capped by the remaining ``LUMEN_DRAIN_S`` budget."""
    from ..utils.env import env_float

    return env_float(DRAIN_ANNOUNCE_ENV, 5.0, minimum=0.0)


def build_one_service(config: LumenConfig, name: str) -> BaseService:
    """Load exactly one service via its ``import_info.registry_class``
    factory (``from_config(service_config, cache_dir)`` classmethod
    contract, reference: ``src/lumen/service.py:12-49``). Shared by first
    boot and background recovery so both exercise the identical path
    (including the ``model_load`` fault point)."""
    from ..testing.faults import faults

    svc_cfg = config.services[name]
    faults.check("model_load", name)
    cls = resolve(svc_cfg.import_info.registry_class)
    logger.info("loading service %r via %s", name, svc_cfg.import_info.registry_class)
    return cls.from_config(svc_cfg, config.metadata.cache_path)


def build_services(
    config: LumenConfig, failed: dict[str, str] | None = None
) -> dict[str, BaseService]:
    """Instantiate every enabled service; services named in ``failed`` (or
    whose construction raises) become :class:`DegradedService` placeholders
    instead of killing their healthy siblings."""
    services: dict[str, BaseService] = {}
    for name, svc_cfg in config.enabled_services().items():
        error = (failed or {}).get(name)
        if error is None:
            try:
                services[name] = build_one_service(config, name)
                continue
            except Exception as e:  # noqa: BLE001 - degrade, don't kill siblings
                logger.exception("service %r failed to load; booting degraded", name)
                error = f"{type(e).__name__}: {e}"
        services[name] = DegradedService(
            name, error, tasks=expected_tasks_for(name, svc_cfg)
        )
    return services


def ensure_models(config: LumenConfig, strict: bool | None = None) -> dict[str, str]:
    """Fetch every enabled model; returns ``{service: error}`` for the
    services whose artifacts could not be made ready. With ``strict``
    (``LUMEN_STRICT_BOOT=1``) any failure aborts, the seed behavior."""
    if strict is None:
        strict = os.environ.get("LUMEN_STRICT_BOOT") == "1"
    report = Downloader(config).download_all()
    failures: dict[str, str] = {}
    for r in report.failures():
        logger.error("model fetch failed: %s/%s (%s): %s", r.service, r.alias, r.model, r.error)
        msg = f"{r.alias} ({r.model}): {r.error}"
        failures[r.service] = f"{failures[r.service]}; {msg}" if r.service in failures else msg
    if failures and strict:
        raise SystemExit(1)
    return failures


def rebuild_service(config: LumenConfig, name: str, skip_download: bool = False) -> BaseService:
    """Recovery path for one degraded service: re-fetch its artifacts and
    reconstruct it. Raises on any failure (the RecoveryManager backs off
    and retries)."""
    if not skip_download:
        report = Downloader(config).download_service(name)
        if not report.ok:
            errs = "; ".join(f"{r.alias}: {r.error}" for r in report.failures())
            raise DownloadError(f"model fetch failed for {name!r}: {errs}")
    return build_one_service(config, name)


def attach_breaker(
    recovery: RecoveryManager, name: str, svc: BaseService
) -> BaseService:
    """Give one live service its circuit breaker (no-op for degraded
    placeholders — they already fast-fail — and when
    ``LUMEN_BREAKER_FAILURES=0`` disables breakers). With
    ``LUMEN_BREAKER_RELOAD=1``, an opening breaker hands the service to
    the RecoveryManager: the same full-reload path a degraded boot uses
    (re-fetch + ``from_config`` + hot-swap), which also replaces any
    wedged batchers the watchdog disabled. Without it, the breaker still
    sheds and half-open-probes — reload stays an operator decision."""
    if isinstance(svc, DegradedService) or breaker_failures() == 0:
        return svc
    reload_on_open = os.environ.get("LUMEN_BREAKER_RELOAD") == "1"

    def on_open() -> None:
        if reload_on_open:
            logger.warning(
                "breaker for %r opened: handing to recovery for a reload", name
            )
            recovery.register(name)

    svc.breaker = CircuitBreaker(name, on_open=on_open)
    return svc


class ServerHandle:
    """A running gRPC server + its lifecycle helpers (returned by ``serve``
    for tests; the CLI blocks on ``wait``)."""

    def __init__(
        self,
        server: grpc.Server,
        port: int,
        mdns: MdnsAdvertiser | None,
        metrics_server=None,
        services: dict | None = None,
        recovery: RecoveryManager | None = None,
        router: HubRouter | None = None,
        autopilot=None,
        federation=None,
    ):
        self.server = server
        self.port = port
        self.mdns = mdns
        self.metrics_server = metrics_server
        # Live view: recovery hot-swaps promoted services into this dict
        # (it is the router's), so teardown closes what is actually running.
        self.services = services if services is not None else {}
        self.recovery = recovery
        self.router = router
        self.autopilot = autopilot
        self.federation = federation
        self._stopped = threading.Event()

    def drain_and_stop(self, drain_s: float | None = None) -> None:
        """Graceful shutdown: refuse new RPCs — the router gate answers
        in-band UNAVAILABLE with a ``lumen-retry-after-ms`` hint while the
        gRPC server keeps accepting, so late clients get a parseable
        back-off instead of a torn connection — let queued and in-flight
        streams complete for up to ``drain_s`` (``LUMEN_DRAIN_S``), flush
        ``server_drain`` flight-recorder events, then tear down. The
        SIGTERM/SIGINT path — shutdown used to drop in-flight work on the
        floor."""
        import time as _time

        if drain_s is None:
            drain_s = drain_budget_s()
        if drain_s <= 0:
            # LUMEN_DRAIN_S=0: the documented immediate-stop behavior —
            # no drain gate, the legacy default grace, no drain events.
            self.stop()
            return
        from ..utils import telemetry

        started = _time.monotonic()
        deadline = started + drain_s
        if self.router is not None:
            self.router.begin_drain(retry_after_s=max(drain_s, 1.0))
        telemetry.record_event(
            "server_drain", "server",
            f"drain started: refusing new RPCs, draining in-flight work "
            f"(budget {drain_s:.0f}s)",
        )
        # Announce hold: a PLANNED shutdown must be gossiped, not
        # discovered. If a front was recently reading our capacity report
        # off Health probes, an idle drain would otherwise tear the
        # listener down before the next poll — and the front would eject
        # us via failover (fed_peer_down incident) instead of re-weighting
        # to zero and prefetching hot keys. Hold (bounded) until a probe
        # is served WITH the draining flag, then a short margin so the
        # front's handoff fetches land while we still answer.
        announce_s = drain_announce_s()
        probe_age = (
            getattr(self.router, "capacity_probe_age", lambda: None)()
            if self.router is not None
            else None
        )
        if (
            announce_s > 0
            and probe_age is not None
            and probe_age <= _DRAIN_WATCHER_STALE_S
        ):
            announce_deadline = min(deadline, started + announce_s)
            while (
                not self.router.drain_announced()
                and _time.monotonic() < announce_deadline
            ):
                _time.sleep(0.05)
            if self.router.drain_announced():
                logger.info(
                    "drain: draining flag gossiped to a watching front "
                    "(%.2fs after SIGTERM)", _time.monotonic() - started,
                )
                _time.sleep(
                    min(1.0, max(deadline - _time.monotonic(), 0.0))
                )
            else:
                logger.info(
                    "drain: no probe observed the draining flag within "
                    "%.1fs; proceeding", announce_s,
                )
        # Hold the gRPC server OPEN while in-flight streams finish: once
        # server.stop() runs, new RPCs die at the transport with no
        # metadata — the in-band hint only exists during this window.
        stragglers = 0
        if self.router is not None:
            while self.router.active_streams() > 0 and _time.monotonic() < deadline:
                _time.sleep(0.05)
            stragglers = self.router.active_streams()
        # Remaining budget (floored small) covers response bytes still on
        # the wire; genuinely stuck streams are aborted at the floor.
        self.stop(grace=max(deadline - _time.monotonic(), 0.5))
        telemetry.record_event(
            "server_drain", "server",
            f"drain complete in {_time.monotonic() - started:.2f}s "
            f"({stragglers} straggler stream(s) past the budget); exiting",
        )

    def stop(self, grace: float = 5.0) -> None:
        if self.autopilot is not None:
            # First of all: the controller must not actuate (park, force a
            # rung, retune a window) against services mid-teardown — and
            # the process-global slot must not keep advertising a dead
            # controller on /autopilot and Health if another server boots
            # in this process later.
            from ..runtime.autopilot import get_autopilot, install_autopilot

            self.autopilot.stop()
            if get_autopilot() is self.autopilot:
                install_autopilot(None)
            self.autopilot = None
        if self.federation is not None:
            # Fleet teardown next: the poller must stop probing (and the
            # process-global slot stop advertising on /peers) before the
            # services it might mark healthy are closed underneath it.
            from ..runtime.federation import get_federation, install_federation
            from ..runtime.result_cache import detach_peer_lookup

            detach_peer_lookup(self.federation.peer_cache_lookup)
            self.federation.close()
            if get_federation() is self.federation:
                install_federation(None)
            self.federation = None
        if self.recovery:
            # Next: a recovery attempt finishing mid-shutdown would swap a
            # fresh service in after the close pass below already ran.
            self.recovery.stop()
        if self.mdns:
            self.mdns.stop()
        if self.metrics_server:
            self.metrics_server.stop()
        # Let in-flight RPCs drain FIRST, then close the services so their
        # batcher threads retire cleanly (instead of dying as daemons
        # mid-batch) and any queued requests are failed loudly rather than
        # silently dropped. grpc sets the stop event only AFTER aborting
        # stragglers at t=grace, so the wait needs margin past the grace
        # window or close() can race still-running handlers.
        self.server.stop(grace).wait(grace + 5.0)
        for name, svc in list(self.services.items()):
            close = getattr(svc, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:  # noqa: BLE001 - best-effort teardown
                    logger.exception("closing service %r failed", name)
        self._stopped.set()

    def wait(self) -> None:
        self.server.wait_for_termination()


def serve(
    config: LumenConfig,
    port_override: int | None = None,
    skip_download: bool = False,
    metrics_port: int | None = None,
) -> ServerHandle:
    from ..runtime import enable_persistent_cache
    from ..runtime.federation import maybe_federation

    enable_persistent_cache()  # warm restarts hit compiled buckets on disk
    # Fleet federation (LUMEN_FED_PEERS / LUMEN_FED_DISCOVER): resolved
    # once at boot, logged once. Unset -> None having done NOTHING — no
    # thread, no gauge, no per-request cost beyond one task-name compare
    # (tier-1 guard) — the single-host path boots byte-identical.
    federation = maybe_federation()
    failed: dict[str, str] = {}
    if not skip_download:
        failed = ensure_models(config)
    services = build_services(config, failed=failed)
    recovery: RecoveryManager | None = None
    if not services:
        if federation is None:
            logger.error("no enabled services selected by deployment config")
            raise SystemExit(1)
        # Front-tier mode: this server owns no models; every Infer stream
        # consistent-hash-routes over the peer set (a front tier speaks
        # the same protocol, so tiers compose).
        router: HubRouter = FederationRouter(federation)
        logger.info(
            "front-tier mode: no local services; routing %d peer(s) with "
            "hop budget %d", len(federation.peers), federation.hops,
        )
    else:
        router = HubRouter(services)

        degraded = sorted(n for n, s in services.items() if isinstance(s, DegradedService))

        def rebuild(n: str) -> BaseService:
            # Recovered/reloaded services get a fresh breaker too: the swap
            # replaces the instance whose breaker (and possibly watchdog-wedged
            # batchers) tripped, and its gauge registration supersedes the old
            # one (last-writer-wins in the metrics registry).
            return attach_breaker(
                recovery, n, rebuild_service(config, n, skip_download=skip_download)
            )

        # Always built (not only on a degraded boot): the per-service circuit
        # breakers can hand a service over for reload at ANY point in the
        # process's life (LUMEN_BREAKER_RELOAD=1).
        recovery = RecoveryManager(router, rebuild=rebuild)
        for name, svc in services.items():
            attach_breaker(recovery, name, svc)
        if degraded:
            logger.warning(
                "booting with %d degraded service(s): %s — healthy siblings keep "
                "serving; background recovery is retrying the failed loads",
                len(degraded), degraded,
            )
            for name in degraded:
                recovery.register(name)
        recovery.start()
        if federation is not None:
            # Peer-aware backend: fleet state rides this hub's Health
            # (lumen-fed-status), and — when this server knows which ring
            # member it is — local cache misses consult the ring owner's
            # cache before computing (the cross-host dedupe tier).
            router.federation = federation
            if federation.self_listed:
                from ..runtime.result_cache import get_result_cache

                get_result_cache().peer_lookup = federation.peer_cache_lookup
                logger.info(
                    "federation: peer-cache lookups enabled (self=%s)",
                    federation.self_name,
                )
            else:
                # Unset OR mislisted: either way the `owner == self`
                # guard cannot work, so no hook (the manager already
                # warned loudly on a mislisted self).
                logger.info(
                    "federation: %s %s — peer-cache lookups disabled on "
                    "this backend (health gossip + Health surfacing only)",
                    "LUMEN_FED_SELF",
                    "unset" if not federation.self_name else "not in peer list",
                )
            # Disaggregated prefill/decode: wire both halves of the KV
            # migration protocol. This host can ANSWER fed_kv_put (decode
            # sink) and DISPATCH migrations (prefill side); which lane it
            # actually plays is the front tier's routing call, driven by
            # each host's LUMEN_FED_ROLE advertisement.
            from ..runtime.federation import fed_role
            from ..utils import disagg

            vlm = next(
                (s for s in services.values() if hasattr(s, "handle_kv_put")),
                None,
            )
            engines = (
                list(getattr(getattr(vlm, "manager", None), "_engines", None) or [])
                if vlm is not None
                else []
            )
            if engines:
                disagg.enable()
                router.kv_migration = vlm
                for eng in engines:
                    eng.migrator = federation.kv_migrate
                logger.info(
                    "federation: KV migration wire enabled on %d engine(s) "
                    "(role=%s)", len(engines), fed_role(),
                )
    if federation is not None:
        federation.start()  # the one background health-poll thread

    # Sized from what the services hold: a stream per decode slot and the
    # base ten (a degraded service that recovers later finds the pool as
    # its boot left it).
    pool = install_handler_pool(grpc_pool_size(decode_slots(services)))
    server = grpc.server(
        futures.ThreadPoolExecutor(
            max_workers=pool.size, thread_name_prefix="grpc"
        ),
        options=GRPC_OPTIONS,
    )
    router.attach_to_server(server)

    host = config.server.host
    port = port_override or config.server.port
    bound = server.add_insecure_port(f"{host}:{port}")
    if bound == 0:
        # Requested port unavailable: fall back to an OS-assigned one
        # (reference behavior, src/lumen/server.py:242-263).
        bound = server.add_insecure_port(f"{host}:0")
        if bound == 0:
            logger.error("could not bind any port on %s", host)
            raise SystemExit(1)
        logger.warning("port %d unavailable; bound %d instead", port, bound)
    server.start()

    # Sidecar starts (and logs its endpoint) BEFORE the readiness line:
    # supervisors treat that line as "fully up", so everything they may
    # immediately query must already be announced. Binds loopback only —
    # profiler control must not be reachable from the network.
    metrics_server = None
    if metrics_port is not None:
        from .observability import MetricsServer

        metrics_server = MetricsServer(port=metrics_port, host="127.0.0.1")
        metrics_server.start()

    # Request-tracing status belongs in the boot log: whether per-stage
    # attribution (GET /traces on the sidecar) is live is a deploy-time
    # fact an operator should not have to probe for.
    from ..utils import trace as request_trace

    if request_trace.enabled():
        logger.info(
            "request tracing ON (sample=%.3g, ring=%d, slowest-%d retained)",
            request_trace.sample_rate(),
            request_trace.trace_ring(),
            request_trace.trace_slow_n(),
        )
    else:
        logger.info("request tracing off (set LUMEN_TRACE_SAMPLE to enable)")

    # Same deploy-time-facts rule for the capacity/SLO layer: whether
    # /stats has windows and whether any SLO objective is armed should be
    # one boot-log line, not a probe.
    from ..utils import telemetry as capacity_telemetry

    objectives = capacity_telemetry.slo_objectives()
    availability = capacity_telemetry.slo_availability()
    if capacity_telemetry.telemetry_enabled():
        logger.info(
            "capacity telemetry ON (bucket=%.0fs, retain=%.0fs); SLO: %s",
            capacity_telemetry.telemetry_bucket_s(),
            capacity_telemetry.telemetry_retain_s(),
            (
                f"{sorted(objectives)} p95 objectives"
                + (f", availability>={availability}" if availability else "")
                if objectives or availability
                else "no objectives (set LUMEN_SLO_<TASK>_P95_MS)"
            ),
        )
    else:
        logger.info("capacity telemetry off (LUMEN_TELEMETRY=0)")

    # Autopilot boot wiring (one-shot log either way): with
    # LUMEN_AUTOPILOT=1 the background controller closes the scale/
    # brownout/window loops over the telemetry spine; default-off keeps
    # tier-1 and unconfigured deployments byte-for-byte unchanged.
    from ..runtime.autopilot import maybe_start_autopilot

    autopilot = maybe_start_autopilot()

    logger.info(
        "serving %d service(s) on %s:%d with %d handler threads: %s",
        len(services), host, bound, pool.size, sorted(services),
    )
    for name, svc in services.items():
        logger.info("  %s [%s] tasks: %s", name, svc.status(), svc.registry.task_names())

    mdns = None
    mdns_cfg = config.server.mdns
    if mdns_cfg and mdns_cfg.enabled:
        mdns = MdnsAdvertiser(
            mdns_cfg.service_name or "lumen-tpu",
            bound,
            properties={"tasks": ",".join(t for s in services.values() for t in s.registry.task_names())},
        )
        mdns.start()
    return ServerHandle(
        server, bound, mdns, metrics_server, services=router.services,
        recovery=recovery, router=router, autopilot=autopilot,
        federation=federation,
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="lumen-tpu", description="lumen-tpu inference server")
    parser.add_argument("--config", required=True, help="path to lumen config YAML")
    parser.add_argument("--port", type=int, default=None, help="override configured port")
    parser.add_argument("--log-level", default="INFO")
    parser.add_argument(
        "--skip-download", action="store_true", help="assume model artifacts are already cached"
    )
    parser.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        help="expose /metrics + jax profiler control on this HTTP port (0 = auto)",
    )
    parser.add_argument(
        "--platform",
        default=None,
        choices=["cpu", "tpu"],
        help="force a JAX platform (e.g. cpu for a hardware-free dry run)",
    )
    args = parser.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    setup_logging(args.log_level)
    config = load_config(args.config)
    handle = serve(
        config,
        port_override=args.port,
        skip_download=args.skip_download,
        metrics_port=args.metrics_port,
    )

    stop_event = threading.Event()

    def _on_signal(signum, frame):  # noqa: ARG001
        logger.info("signal %d received; shutting down", signum)
        stop_event.set()

    signal.signal(signal.SIGINT, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)
    while not stop_event.wait(timeout=1.0):
        pass
    # Graceful drain (LUMEN_DRAIN_S): late RPCs answer UNAVAILABLE with a
    # retry-after hint, in-flight work completes, a server_drain event
    # lands in the flight recorder, then the process exits.
    handle.drain_and_stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
