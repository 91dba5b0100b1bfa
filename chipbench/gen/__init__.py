"""Traffic generators, each reading the mix files that name it."""
