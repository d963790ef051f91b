"""repro: CXL-GPU reproduction package."""
