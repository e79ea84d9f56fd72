# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# Kernels in the tree (see ARCHITECTURE.md "Kernels"):
#   next_event.py — fused masked (min, argmin) next-event reduction
#   flash_attention.py / rwkv6_scan.py — model-stack kernels
#   ops.py        — public adapters + the use_pallas resolution switch
#
# Nothing is imported here: the vec engines' plain path must not load
# Pallas, so each kernel module is imported where its route is taken.
