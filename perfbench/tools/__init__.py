"""Hand tools of the benchmark; the driver runs none of them."""
