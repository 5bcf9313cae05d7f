"""The benchmark's harness: cell resolution, the device trace and its
reduction, result printing.  Nothing here knows a cell by name."""
