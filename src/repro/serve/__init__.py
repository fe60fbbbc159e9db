"""repro.serve — the simulation service: continuous batching over ensemble lanes."""
from .sim_service import FinishedSim, SimRequest, SimService
