"""Traffic kinds: one generator per kind, each reading its traffic file."""
