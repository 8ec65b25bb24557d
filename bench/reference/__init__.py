"""Plain float32 references of the configurations, one module per model
type; ``common`` holds what they share."""
