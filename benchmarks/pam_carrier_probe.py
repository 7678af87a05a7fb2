"""The bf16 jnp PAM matmul engine on its two integer carriers: int16 (the
format's own width, ``BFLOAT16``) and int32 (``BFLOAT16.widened``, what the
kernels use). Prints one JSON line per carrier: whether its output equals
the int16 carrier's bit for bit, seconds per call (median of 5 after the
compile), and the bytes the compiled program accesses (XLA cost analysis).

    PYTHONPATH=src python -m benchmarks.pam_carrier_probe [--shape M K N]

Runs on whatever backend JAX picks (the CPU here, a TPU through the chip
tool). The default shape is smollm-135m's MLP up-projection at 4 x 1024
tokens.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import floatbits as fb
from repro.core.matmul import _pam_matmul_value


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=3, default=(4096, 576, 1536),
                    metavar=("M", "K", "N"))
    m, k, n = ap.parse_args().shape
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.bfloat16)
    base = None
    for name, fmt in (("int16", fb.BFLOAT16), ("int32", fb.BFLOAT16.widened)):
        f = jax.jit(lambda a, b, fmt=fmt: _pam_matmul_value(a, b, fmt=fmt))
        compiled = f.lower(a, b).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        out = np.asarray(jax.block_until_ready(compiled(a, b)))
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(a, b))
            times.append(time.perf_counter() - t0)
        base = out if base is None else base
        print(json.dumps({
            "carrier": name, "backend": jax.default_backend(),
            "shape": [m, k, n], "bit_equal_to_int16":
                bool(np.array_equal(out.view(np.uint16),
                                    base.view(np.uint16))),
            "s_per_call": float(np.median(times)),
            "bytes_accessed": float(cost.get("bytes accessed", -1))}),
            flush=True)


if __name__ == "__main__":
    main()
