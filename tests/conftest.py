"""Test harness.

- Forces JAX onto the CPU backend with 8 virtual devices BEFORE any jax
  import, so sharding/pjit tests exercise a simulated v5e-8 mesh (the
  reference's "multi-node without a cluster" testing discipline,
  SURVEY.md §4) without TPU hardware.
- Hermetic state: in-memory sqlite + memory bus + strong test secrets
  (reference `tests/conftest.py:22-88` forces in-memory SQLite + test
  secrets the same way).
- Runs ``async def`` tests natively (no pytest-asyncio in the image).
"""

import asyncio
import inspect
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# persistent compilation cache off, for this process and the children it
# spawns: engines would otherwise fill <checkout>/.jax_cache with CPU
# executables (engine.apply_compile_cache)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

os.environ["MCPFORGE_DATABASE_URL"] = "sqlite:///:memory:"
os.environ["MCPFORGE_BUS_BACKEND"] = "memory"
os.environ["MCPFORGE_JWT_SECRET_KEY"] = "unit-test-jwt-secret-0123456789abcdef"
os.environ["MCPFORGE_AUTH_ENCRYPTION_SECRET"] = "unit-test-enc-secret-0123456789abcdef"
os.environ["MCPFORGE_DEV_MODE"] = "true"
os.environ["MCPFORGE_ENVIRONMENT"] = "development"
os.environ["MCPFORGE_TPU_LOCAL_MODEL"] = "llama3-test"
os.environ["MCPFORGE_OTEL_EXPORTER"] = "memory"

import pytest


def pytest_pyfunc_call(pyfuncitem):
    """Execute async test functions with asyncio.run (no plugin needed)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        sig = inspect.signature(fn)
        kwargs = {k: v for k, v in pyfuncitem.funcargs.items() if k in sig.parameters}
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(scope="module")
def model_variant():
    """``model_variant("mixtral-test", moe_block=8)``: a changed copy of a
    model config, registered in ``MODEL_CONFIGS`` under a name that says the
    change (as ``benchmark/harness`` registers a cell's) and taken out again
    when the test module ends. Returns the name, for
    ``EngineConfig(model=...)``."""
    import dataclasses

    from mcp_context_forge_tpu.tpu_local.models import MODEL_CONFIGS

    patch = pytest.MonkeyPatch()

    def register(model: str, **changes) -> str:
        name = model + "+" + ",".join(
            f"{field}={value}" for field, value in sorted(changes.items()))
        patch.setitem(MODEL_CONFIGS, name, dataclasses.replace(
            MODEL_CONFIGS[model], name=name, **changes))
        return name

    yield register
    patch.undo()


@pytest.fixture()
def settings():
    from mcp_context_forge_tpu.config import load_settings

    return load_settings(env_file=None)
