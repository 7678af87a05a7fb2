"""Pallas TPU kernels for the paper's compute hot-spots.

pa_prims        — shared PA bit-twiddling primitives (scalar helpers + the
                  grouped PAM tile product) every kernel family imports
autotune        — shared shape-bucketed tile-parameter registry
pam_matmul      — grouped k-block bit-exact PAM matrix multiply with a
                  batched grid and Pallas backward (VPU; DESIGN.md §2)
pam_eltwise     — fused elementwise pam/padiv/paexp2/palog2
pa_softmax      — fused row softmax in PA arithmetic (autotuned row blocks)
flash_attention — online-softmax attention: the float kernel, plus the
                  fused PAM flash attention (scores -> PA-softmax -> AV in
                  one streaming kernel with a recompute Pallas backward;
                  DESIGN.md §4) — kills the S*T HBM traffic the roofline
                  identified as the training memory bottleneck

Each kernel ships ops.py (jit'd public wrapper) and ref.py (pure-jnp oracle);
all are validated in interpret mode on CPU against their oracles
(tests/test_kernels.py, tests/test_pam_matmul_engine.py,
tests/test_pam_attention.py) and compiled for TPU v5e without a chip
(tests/test_tpu_compile.py). The platform alone picks compiled TPU or CPU
interpret, per call, in ``_backend.use_interpret()`` — never frozen at
import time.
"""
