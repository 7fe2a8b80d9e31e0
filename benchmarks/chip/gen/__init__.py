"""Data and traffic generators of the on-chip benchmark."""
