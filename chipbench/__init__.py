"""Chip benchmark of the served path: client -> StraightLineRouter ->
EngineLoop -> PagedInferenceEngine, one cell per run (see run.py)."""
