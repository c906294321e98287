# Compute hot-spot kernels (the paper's conv-as-GEMM loop, TPU-native)
# plus the serving execution layer on top of them:
#
#   conv_fused.py            fused implicit-GEMM conv + epilogue
#   dwconv.py                depthwise conv + epilogue on the vector unit
#   autotune.py              descriptor-keyed (bm, bn, bk) block tuner
#   backend.py               the route: xla (reference) | pallas_fused
#                            (served), with automatic XLA fallback
#   config.py                platform-resolved interpret defaults
#   ops.py / ref.py          flash-decode/SSD wrappers + pure-jnp oracles
#   flash_decode.py, ssd.py  scaling-substrate kernels (DESIGN.md §4)
