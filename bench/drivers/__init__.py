"""Drivers: how a cell's traffic reaches the program (one file each)."""
