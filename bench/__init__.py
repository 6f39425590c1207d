"""The on-chip benchmark of the GeckOpt serving path (see README.md)."""
