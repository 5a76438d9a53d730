"""Drivers: how a kind of cell is set up, warmed, measured and checked.
Found by the name in the cell's file (``"driver": "train_tree"``)."""
