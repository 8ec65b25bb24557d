"""How each model type's configuration file becomes the program's
``ModelConfig``, one module per model type."""
