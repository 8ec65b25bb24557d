"""Model FLOPs of one train step, one module per model type."""
