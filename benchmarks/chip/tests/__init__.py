"""Tests of the chip benchmark that run without a chip."""
