"""Tools that made the benchmark's data files; no run imports them."""
