"""On-chip benchmark of the EVA VQ decode server (see BENCHMARK.json)."""
