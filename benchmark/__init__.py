"""The benchmark: the game's AOI tick on the chip (see BENCHMARK.json)."""
