"""Core protocol library: the paper's contribution (LAD / Com-LAD).

Layout:
  task_matrix.py  — cyclic task matrix S_hat (Lemma 1 optimal), DRACO's
                    fractional repetition, per-round randomized assignment
  coding.py       — eq.-(5) gradient encoder, DRACO majority-vote decoder
  aggregators.py  — kappa-robust rules (CWTM, median, Krum, geomed, MCC, TGN,
                    NNM pre-aggregation)
  compression.py  — unbiased compressors (random sparsification, stochastic
                    quantization) + shared-mask and top-k variants
  attacks.py      — Byzantine attack library (sign-flip, ALIE, IPM, ...)
  byzantine.py    — LAD/Com-LAD meta-algorithm (single-process protocol round)
  participation.py— partial-participation / straggler fault model (per-round
                    erasure masks from deterministic key-derived schedules)
  engine.py       — scan-compiled multi-round trajectory engine
  scenarios.py    — declarative method x attack x aggregator x compressor grid
  theory.py       — Lemmas 1-4 / Theorems 1-2 constants and error terms
"""
from repro.core import aggregators, attacks, coding, compression, task_matrix, theory
from repro.core.byzantine import ProtocolConfig, protocol_round
from repro.core.engine import TrajectoryResult, protocol_rounds, run_trajectory
from repro.core.participation import ParticipationSpec
from repro.core.scenarios import (
    Scenario,
    grid_finals,
    participation_sweep,
    run_grid,
    run_scenario,
    section7_grid,
)

__all__ = [
    "aggregators",
    "attacks",
    "coding",
    "compression",
    "task_matrix",
    "theory",
    "ProtocolConfig",
    "ParticipationSpec",
    "protocol_round",
    "participation_sweep",
    "TrajectoryResult",
    "protocol_rounds",
    "run_trajectory",
    "Scenario",
    "grid_finals",
    "run_grid",
    "run_scenario",
    "section7_grid",
]
