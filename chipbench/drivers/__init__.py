"""One module per kind of traffic: run(cell, seed, seconds, trace,
platform) -> record. The parent half never touches JAX; the half that
runs in the process owning the chip is named *_on_chip."""
