"""repro: JAX/Pallas reproduction of Homunculus data-plane ML pipelines."""
