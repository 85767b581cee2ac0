"""Importable stand-in services for integration tests.

The built-in :class:`~lumen_tpu.serving.echo.EchoService` hard-codes its
task names, so a hub config with two echo-backed services would collide on
the route table. :class:`SecondaryEchoService` is the same diagnostic
service under distinct task keys — resilience tests point one config
service at each and fault-inject only one of them.
"""

from __future__ import annotations

import json

from ..core.config import ServiceConfig
from ..serving.base_service import BaseService
from ..serving.registry import TaskDefinition, TaskRegistry


class SecondaryEchoService(BaseService):
    """Echo semantics under ``echo2*`` task names (see module docstring)."""

    def __init__(self, service_name: str = "echo2"):
        registry = TaskRegistry(service_name)
        registry.register(
            TaskDefinition(
                name="echo2",
                handler=self._echo,
                description="return the payload unchanged",
                input_mimes=("application/octet-stream", "text/plain"),
                output_mime="application/octet-stream",
            )
        )
        registry.register(
            TaskDefinition(
                name="echo2_meta",
                handler=self._echo_meta,
                description="return request meta as JSON",
                output_mime="application/json",
            )
        )
        super().__init__(registry)

    @classmethod
    def expected_tasks(cls, service_config: ServiceConfig) -> list[str]:  # noqa: ARG003
        return ["echo2", "echo2_meta"]

    @classmethod
    def from_config(cls, service_config: ServiceConfig, cache_dir: str) -> "SecondaryEchoService":  # noqa: ARG003
        return cls()

    def capability(self):
        return self.registry.build_capability(model_ids=["echo2"], runtime="none")

    def _echo(self, payload: bytes, mime: str, meta: dict[str, str]):  # noqa: ARG002
        return payload, mime or "application/octet-stream", {}

    def _echo_meta(self, payload: bytes, mime: str, meta: dict[str, str]):  # noqa: ARG002
        return json.dumps(meta, sort_keys=True).encode(), "application/json", {}


class SlowEchoService(BaseService):
    """Echo with a handler-side sleep (``sleep_s`` request meta, default
    0.3s) — the in-flight work the graceful-drain tests hold open across a
    SIGTERM to prove shutdown completes it instead of dropping it."""

    def __init__(self, service_name: str = "slow"):
        registry = TaskRegistry(service_name)
        registry.register(
            TaskDefinition(
                name="slow_echo",
                handler=self._slow_echo,
                description="sleep sleep_s (meta), then echo",
                input_mimes=("application/octet-stream", "text/plain"),
                output_mime="application/octet-stream",
            )
        )
        super().__init__(registry)

    @classmethod
    def expected_tasks(cls, service_config: ServiceConfig) -> list[str]:  # noqa: ARG003
        return ["slow_echo"]

    @classmethod
    def from_config(cls, service_config: ServiceConfig, cache_dir: str) -> "SlowEchoService":  # noqa: ARG003
        return cls()

    def capability(self):
        return self.registry.build_capability(model_ids=["slow-echo"], runtime="none")

    def _slow_echo(self, payload: bytes, mime: str, meta: dict[str, str]):
        import time

        time.sleep(float(meta.get("sleep_s", "0.3")))
        return payload, mime or "application/octet-stream", {"slow": "1"}
