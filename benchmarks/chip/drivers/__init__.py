"""Drivers: one module per way of driving the program (``run(...)``)."""
