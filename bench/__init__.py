"""The chip benchmark: one harness driven by the data files beside it."""
