"""Boot the hub in this process from a configuration file, as
``generate_config(preset, tier)`` gives it but for what the file changes:
the families served, the model names, backend settings, and ``warmup`` on
for the driven family only."""

from __future__ import annotations

import os


def hub_config(cfg: dict, cache_root: str, names: dict[str, str], driven: str):
    from lumen_tpu.app.config_gen import generate_config
    from lumen_tpu.core.config import validate_config_dict

    raw = generate_config(cfg["preset"], tier=cfg["tier"], cache_dir=cache_root, mdns=False).model_dump(
        exclude_none=True
    )
    families = list(cfg["services"])
    raw["deployment"]["services"] = families
    raw["services"] = {f: raw["services"][f] for f in families}
    for family in families:
        (model,) = raw["services"][family]["models"].values()
        model["model"] = names[family]
        settings = raw["services"][family]["backend_settings"]
        settings.update(cfg.get("backend_settings", {}).get(family, {}))
        settings["warmup"] = family == driven
    return validate_config_dict(raw)


def boot(cfg: dict, cache_root: str, names: dict[str, str], driven: str):
    """(server handle, each family's backend settings as validated)."""
    from lumen_tpu.serving.server import serve

    config = hub_config(cfg, cache_root, names, driven)
    settings = {f: config.services[f].backend_settings.model_dump(exclude_none=True) for f in names}
    return serve(config, port_override=0, skip_download=True), settings


def shutdown_pools() -> None:
    """Stop the decode pool's worker processes (the hub's drain leaves them)."""
    from lumen_tpu.runtime.decode_pool import shutdown_decode_pool

    shutdown_decode_pool()


def counters() -> dict:
    """The program's own counters, gauges and per-task sums, as it keeps them."""
    from lumen_tpu.utils.metrics import metrics

    return metrics.snapshot()


def apply_env(cfg: dict) -> None:
    for key, value in cfg.get("env", {}).items():
        os.environ[key] = str(value)
