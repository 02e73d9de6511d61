"""One module per model family: key map, model FLOPs and plain reference."""
