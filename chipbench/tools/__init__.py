"""Builder's tools: nothing here runs in a cell's run."""
