"""Data substrate: QUEST synthetic generator and the Table-1 dataset
stand-ins."""
