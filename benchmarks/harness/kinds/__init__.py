"""One driver module per kind of cell.  A cell's file names its ``kind``;
``run.py`` imports ``harness.kinds.<kind>`` and calls its ``run``.  A new
kind is a new module here and no edit to another."""
