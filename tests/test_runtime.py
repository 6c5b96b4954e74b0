"""No fallback that hides the device, and one place for the compile cache."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from throttlecrab_tpu.tpu.table import SERVED_VARIANTS

ROOT = Path(__file__).resolve().parent.parent


def _run(code_or_args, env_extra=None, drop=(), timeout=300):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *code_or_args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "default"])
def test_compile_cache_directory(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the fixed
    <repo>/.jax_cache.  Nothing is compiled, so nothing is written."""
    code = (
        "import jax\n"
        "from throttlecrab_tpu.runtime import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {"JAX_ENABLE_COMPILATION_CACHE": "true"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = _run(["-c", code], env, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert r.returncode == 0, r.stderr[-2000:]
    want = str(tmp_path) if env_dir else str(ROOT / ".jax_cache")
    assert r.stdout.split() == [want, want]


@pytest.mark.parametrize(
    "state,tail",
    [("OK", ""), ("draining", ""), ("degraded", " checkpoint_age_s=3.2"),
     (None, None)],
    ids=["ok", "draining", "degraded-checkpoint", "bare"],
)
def test_parse_health_reads_the_device(state, tail):
    """Every /health body the server sends names its device; a body
    without one (a foreign or pre-boot server) parses to None."""
    from throttlecrab_tpu.runtime import (
        device_info, health_suffix, parse_health,
    )

    if state is None:
        assert parse_health("OK") is None
        return
    body = f"{state} {health_suffix()}{tail}"
    assert parse_health(body) == device_info()


def test_bench_fails_without_tpu():
    """No CPU fallback: without --cpu, bench.py needs a TPU."""
    r = _run(["bench.py", "--quick"])
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert r.stdout == ""


@pytest.mark.parametrize(
    "with_degen,compact", SERVED_VARIANTS, ids=["w32", "general"]
)
@pytest.mark.parametrize(
    "shape",
    [dict(), dict(shards=2), dict(shards=2, tenant_max=0)],
    ids=["single", "sharded-tenants", "sharded"],
)
def test_served_launch_compiles_on_the_tables_devices(
    shape, with_degen, compact
):
    """The table the server boots compiles the launch its served window
    dispatches (the packed scan, or the sharded scan step) in each
    served tier, onto the table's own devices: compile_launch needs no
    more of a table than the chip-compile tests describe."""
    from throttlecrab_tpu.server.config import Config
    from throttlecrab_tpu.server.store import create_limiter
    from throttlecrab_tpu.tpu.kernel import (
        gcra_scan_packed_acc,
        gcra_scan_packed_ins,
    )

    table = create_limiter(
        Config(http=True, store_capacity=4096, **shape)
    ).table
    if not shape:
        assert table._packed_launch()[0] is (
            gcra_scan_packed_ins if table.insight else gcra_scan_packed_acc
        )
    compiled = table.compile_launch(
        1, 16, with_degen=with_degen, compact=compact
    )
    assert compiled.output_shardings[0].device_set == (
        table.state.sharding.device_set
    )
