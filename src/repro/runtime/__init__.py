from repro.runtime.checkpoint import CheckpointManager  # noqa: F401
