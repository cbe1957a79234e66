"""Distributed graph signal processing via Chebyshev polynomial approximation."""
