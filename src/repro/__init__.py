"""repro — RAGdb reproduction grown into a jax_pallas serving system."""
