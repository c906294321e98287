# Compute hot-spot kernels (the paper's conv-as-GEMM loop, TPU-native)
# plus the serving execution layer on top of them:
#
#   gemm.py / im2col.py      unfused conv-as-GEMM pair (§V-A/V-C)
#   conv_fused.py            fused implicit-GEMM conv + epilogue (PR 3)
#   dwconv.py                depthwise conv + epilogue on the vector unit
#   autotune.py              descriptor-keyed (bm, bn, bk) block tuner
#   backend.py               per-node backend selection (xla | pallas |
#                            pallas_fused) with automatic XLA fallback
#   config.py                platform-resolved interpret defaults
#   ops.py / ref.py          public wrappers + pure-jnp oracles
#   flash_decode.py, ssd.py  scaling-substrate kernels (DESIGN.md §4)
