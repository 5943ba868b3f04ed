"""repro: MTTKRP/CP-ALS framework + LM substrate on JAX."""
