"""R-GCN's typed aggregation: relation-grouped row blocks, one relation's
block-diagonal weights per block (``kernel.py``), and the wrapper that
gathers the rows, runs the kernel and sums the messages into their
destinations (``ops.py``)."""
