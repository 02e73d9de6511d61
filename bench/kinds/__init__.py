"""One generator per kind of traffic, found by the kind's name."""
