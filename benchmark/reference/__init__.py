"""The plain reference check and its control (imports nothing of simtpu)."""
