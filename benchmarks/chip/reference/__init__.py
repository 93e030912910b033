"""The comparison that decides ``correct`` (``compare.py``), and the float32
building blocks that the families' plain references share (``ops.py``);
each reference is its family's (``families/<family>.py``), independent of
the program under test."""
