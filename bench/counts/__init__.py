"""Operation and byte counts, from shapes alone, one module per operation."""
