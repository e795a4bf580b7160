"""What every cell shares: resolution by name, peaks, costs, spans, traces."""
