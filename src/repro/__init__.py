"""repro — mixed-bit-width sparse CNN accelerator reproduction grown
into a jax LM training/serving substrate."""
