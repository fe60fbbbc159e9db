"""repro — BioDynaMo-JAX: TPU-native high-performance agent-based simulation engine.

Reproduction (and TPU adaptation) of:
  "High-Performance and Scalable Agent-Based Simulation with BioDynaMo"
  Breitwieser, Hesam, Rademakers, Gomez-Luna, Mutlu (CS.DC 2023)

Package layout:
  repro.core      -- the paper's engine (grid neighbor search, Morton sort,
                     parallel add/remove, static-region detection, forces),
                     the distributed and ensemble engines, and checkpointing
  repro.kernels   -- the Pallas K1 contact-force kernel and its oracle
  repro.serve     -- SimService: continuous batching over ensemble lanes
  repro.launch    -- the simulate and sim_serve CLIs, the compile cache
"""

__version__ = "1.0.0"
