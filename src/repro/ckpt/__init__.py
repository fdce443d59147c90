from .checkpoint import (
    CheckpointCorruptError,
    CheckpointFormatError,
    CheckpointManager,
    cleanup,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)

__all__ = ["CheckpointCorruptError", "CheckpointFormatError",
           "CheckpointManager", "cleanup",
           "latest_step", "restore_checkpoint", "save_checkpoint"]
