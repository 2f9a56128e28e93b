"""Distribution layer: sharding rules, quantized collectives, pipelining.

The scale-out counterpart of the paper's streaming W1A8 dataflow (DESIGN.md
§9): the same compensation/scale split that survives the mapping to the
binary PE must survive the mapping to a pod —

  * ``sharding``    — PartitionSpec rules for every param leaf of every arch
                      (model axis on attention/FFN projections, (data, model)
                      on MoE expert stacks),
  * ``collectives`` — int8-on-the-wire gradient all-reduce with per-leaf
                      scales (the W1A8 wire format applied to collectives),
  * ``pipeline``    — GPipe microbatch pipelining over a mesh axis.
"""
from repro.dist import collectives, pipeline, sharding  # noqa: F401
