"""The chip benchmark of the k-mer counter and query service (see run.py)."""
