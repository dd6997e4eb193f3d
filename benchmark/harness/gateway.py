"""Start the system under test: the gateway app as ``cli serve`` builds it,
on a real socket on 127.0.0.1, in the one process that holds the chip.

Copied from ``chip_smoke.py: phase_gateway`` (not imported: the yardstick
lives under the benchmark's own directory). The only things the harness puts
between the program and the socket are stated here:

* ``register_model``: the configuration's file becomes the engine's model
  config before ``build_app`` (a depth cut is a file, not an edit). Which keys
  mean what is the configuration's family's to say
  (``benchmark/families/<family>.py``); this module names no model family;
* ``RenderEveryToken``: the repo has no vocabulary file, and its byte-level
  tokenizer drops every sampled id above 255 — with random weights nearly all
  of them — so a stream would carry no content event. The harness renders
  each id as one character, so that each token is one SSE event, as with a
  real vocabulary. Prompts are still encoded by the program's tokenizer.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Any, AsyncIterator

from .manifest import ROOT

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GATEWAY_ENV = {
    "MCPFORGE_DATABASE_URL": "sqlite:///:memory:",
    "MCPFORGE_BUS_BACKEND": "memory",
    "MCPFORGE_OTEL_EXPORTER": "none",
    "MCPFORGE_LOG_LEVEL": "WARNING",
    "MCPFORGE_GATEWAY_HEALTH_INTERVAL": "3600",
}
# settings every cell shares; what follows from the model sits in the
# configuration's file, what follows from the traffic in the mix's file
ENGINE_ENV = {
    "MCPFORGE_TPU_LOCAL_ENABLED": "true",
    "MCPFORGE_TPU_LOCAL_WARMUP": "true",
    "MCPFORGE_TPU_LOCAL_WARMUP_MODE": "full",
    "MCPFORGE_TPU_LOCAL_EMBEDDING_MODEL": "encoder-mini",
    "MCPFORGE_TPU_LOCAL_ENCODER_MAX_BATCH": "4",
    # the XLA cost capture lowers every decode program a second time and
    # feeds only the live gauges, which the benchmark does not read
    "MCPFORGE_TPU_LOCAL_COST_ANALYSIS": "false",
}


def engine_env(name: str, *groups: dict[str, Any]) -> dict[str, str]:
    """``MCPFORGE_TPU_LOCAL_*`` for the ``engine`` groups of a configuration
    and a mix (later groups win)."""
    env = {**GATEWAY_ENV, **ENGINE_ENV, "MCPFORGE_TPU_LOCAL_MODEL": name}
    for group in groups:
        for key, value in group.items():
            if isinstance(value, bool):
                value = "true" if value else "false"
            elif isinstance(value, (list, tuple)):
                value = ",".join(str(v) for v in value)
            env[f"MCPFORGE_TPU_LOCAL_{key.upper()}"] = str(value)
    return env


def register_model(name: str, config: dict[str, Any]):
    """``MODEL_CONFIGS[name]`` from the configuration file's keys, as its
    family (``"family"``, absent: ``"llama"``) reads them."""
    from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS

    from .. import families

    model = families.of(config).model_config(name, config)
    MODEL_CONFIGS[name] = model
    return model


class RenderEveryToken:
    """The engine's tokenizer with a ``decode`` that renders every id."""

    def __init__(self, inner) -> None:
        self._inner = inner

    def __getattr__(self, name: str) -> Any:
        return getattr(self._inner, name)

    def decode(self, ids: list[int]) -> str:
        return "".join(chr(33 + i % 94) for i in ids)


class CompileMeter:
    """Process-wide XLA compile count/seconds and persistent-cache hits."""

    def __init__(self) -> None:
        from jax import monitoring

        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += float(duration)

    def _on_event(self, event: str, **_kw: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def facts(self) -> dict[str, Any]:
        return {"compiles": self.count, "compile_s": round(self.seconds, 3),
                "cache_hits": self.cache_hits}


def device_facts() -> dict[str, Any]:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """``peak_bytes_in_use`` on the fullest device (0 where the backend keeps
    no statistics: the CPU of a rehearsal)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
               for d in jax.devices())


@contextlib.asynccontextmanager
async def serving(env: dict[str, str]) -> AsyncIterator[dict[str, Any]]:
    """Build the app, bind 127.0.0.1, yield what a client needs, tear down."""
    from aiohttp import web

    from mcp_context_forge_tpu.config import get_settings, reset_settings_cache
    from mcp_context_forge_tpu.gateway.app import build_app, install_event_loop
    from mcp_context_forge_tpu.utils import jwt

    os.environ.update(env)
    reset_settings_cache()
    settings = get_settings()
    install_event_loop(settings.gw_event_loop)
    started = time.monotonic()
    app = await build_app(settings)
    engine = app["tpu_engine"]
    engine.tokenizer = RenderEveryToken(engine.tokenizer)
    runner = web.AppRunner(app)
    await runner.setup()
    try:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        host, port = runner.addresses[0][:2]
        token = jwt.create_token(
            {"sub": settings.platform_admin_email}, settings.jwt_secret_key,
            settings.jwt_algorithm, expires_minutes=60,
            audience=settings.jwt_audience, issuer=settings.jwt_issuer)
        yield {"app": app, "engine": engine,
               "base_url": f"http://{host}:{port}",
               "headers": {"Authorization": f"Bearer {token}"},
               "build_s": time.monotonic() - started}
    finally:
        await runner.cleanup()      # stops the engine's dispatch thread
