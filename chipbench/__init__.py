"""chipbench — the chip benchmark of this repository (see README.md)."""
