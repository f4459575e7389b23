"""repro.dist — the distribution layer (DESIGN.md §5, §14).

Six modules, mirroring the paper's approximation philosophy applied to the
interconnect instead of the multiplier datapath:

  meshctx       process-global mesh registry + activation-sharding helpers
  sharding      name-pattern partition rules for params / opt state / batches
  collectives   approximation-as-communication: quantized + error-feedback
                gradient compression and an int8 ring all-reduce
  hlo_analysis  trip-count-aware HLO text walker (dot FLOPs, collective bytes)
  elastic       surviving-device-count -> (pod, data, model) rescale planning
  fleet         replica fleet supervision for elastic sharded serving —
                routing, replica-loss recovery, rescale (docs/distributed_serving.md)

Importing this package also pins the CPU platform for host-mesh dry-runs
(``compat.install``).
"""

from repro.dist import compat as _compat

_compat.install()

from repro.dist import meshctx  # noqa: E402  (the pin must come first)

__all__ = ["meshctx", "compat"]
