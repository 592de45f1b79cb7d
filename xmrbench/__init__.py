"""On-chip benchmark of XMR tree serving; entry point ``xmrbench/run.py``."""
