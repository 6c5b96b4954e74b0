"""Compare packed-buffer layouts for the scan kernel on the real device.

Hypothesis: [K, B, 9] forces strided minor-dim slices per field (bad TPU
layout); [K, 9, B] gives each field a contiguous lane vector.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import throttlecrab_tpu  # noqa: F401
import jax

if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

from throttlecrab_tpu.tpu.kernel import _gcra_body, _U32, gcra_scan, gcra_scan_packed
from throttlecrab_tpu.tpu.table import BucketTable

dev = jax.devices()[0]
print(f"device: {dev}", file=sys.stderr)

B, K, CAP = 4096, 64, 1 << 21
rng = np.random.default_rng(3)

slots = rng.integers(0, CAP - 1, (K, B)).astype(np.int32)
em = np.full((K, B), 20_000_000, np.int64)
tol = np.full((K, B), 1_000_000_000, np.int64)
now = np.full(K, 1_753_000_000_000_000_000, np.int64)


def join(lo, hi):
    return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) & _U32)


@partial(jax.jit, donate_argnums=(0,))
def scan_fieldmajor(state, packed, now):
    """packed: i32[K, 9, B] — field-major."""

    def step(state, kb):
        p, now_k = kb
        batch = (
            p[0],
            p[1].astype(jnp.int64),
            (p[2] & 1) != 0,
            join(p[3], p[4]),
            join(p[5], p[6]),
            join(p[7], p[8]),
            (p[2] & 2) != 0,
            now_k,
        )
        return _gcra_body(state, batch, with_degen=False, compact=True)

    return jax.lax.scan(step, state, (packed, now.astype(jnp.int64)))


def pack_rowmajor():
    out = np.zeros((K, B, 9), np.int32)
    out[..., 0] = slots
    out[..., 2] = 3
    out[..., 3] = (em & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    out[..., 4] = (em >> 32).astype(np.int32)
    out[..., 5] = (tol & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    out[..., 6] = (tol >> 32).astype(np.int32)
    out[..., 7] = 1
    return out


pk_row = pack_rowmajor()
pk_field = np.ascontiguousarray(pk_row.transpose(0, 2, 1))


def bench(label, fn, n=6):
    np.asarray(fn())  # compile, fully drained before timing
    np.asarray(fn())
    # fetched per launch (serialized round trips)
    t0 = time.perf_counter()
    for _ in range(n):
        np.asarray(fn())
    dt_b = (time.perf_counter() - t0) / n
    # enqueued back-to-back, all outputs fetched at the end (pipelined)
    t0 = time.perf_counter()
    outs = [fn() for _ in range(n)]
    for o in outs:
        np.asarray(o)
    dt_q = (time.perf_counter() - t0) / n
    print(
        f"{label}: fetched {dt_b*1e3:8.2f} ms  queued {dt_q*1e3:8.2f} ms"
        f"  ({K*B/dt_q/1e6:6.2f} M dec/s queued)"
    )


# --- row-major packed, numpy arg ------------------------------------------
table = BucketTable(CAP)


def f_row():
    table.state, out = gcra_scan_packed(
        table.state, jnp.asarray(pk_row), jnp.asarray(now),
        with_degen=False, compact=True,
    )
    return out


bench("row-major  [K,B,9] numpy arg ", f_row)

# --- field-major packed, numpy arg ----------------------------------------
table2 = BucketTable(CAP)


def f_field():
    table2.state, out = scan_fieldmajor(
        table2.state, jnp.asarray(pk_field), jnp.asarray(now)
    )
    return out


bench("field-major [K,9,B] numpy arg", f_field)

# --- unpacked eight-array scan, device-resident ---------------------------
table3 = BucketTable(CAP)
dev_args = [
    jax.device_put(a, dev)
    for a in (
        slots, np.zeros((K, B), np.int32), np.ones((K, B), bool),
        em, tol, np.ones((K, B), np.int64), np.ones((K, B), bool), now,
    )
]
jax.block_until_ready(dev_args)


def f_unpacked():
    table3.state, out = gcra_scan(
        table3.state, *dev_args, with_degen=False, compact=True
    )
    return out


bench("unpacked 8-array, resident   ", f_unpacked)
