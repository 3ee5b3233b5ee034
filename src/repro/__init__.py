"""repro: collective embedding in training DAGs (see DESIGN.md)."""
