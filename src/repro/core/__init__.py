# The paper's primary contribution: an asynchronous graph-processing
# architecture, adapted TPU-natively (see DESIGN.md §2).
#   graph/cluster  — Fig.4 compile-time steps 1–4 (topology → clusters →
#                    dependencies → placement)
#   repro.algebra  — the NALE MAC/comparator datapath algebra: a leaf
#                    module that core/ and kernels/ both read
#   engine         — sync (BSP) vs async (cluster-dataflow, Gauss-Seidel)
#   algorithms     — SSSP, BFS, DFS, PageRank, MiniTri, CC
#   isa/compile    — the specialized ISA + step-5 codegen
#   power          — cycle & energy models for NALE / CPU / GPU classes
#   placement      — multi-device halo-exchange engine (shard_map)

from . import algorithms, api, cluster, compile, engine, graph, isa, \
    oracles, placement, power  # noqa: F401
