"""The benchmark's yardstick: window, result line, trace reduction, counts,
peaks, plain reference and data generator.  Nothing here imports the
program except ``kinds/`` drivers, which take the system under test and its
compile ledger and nothing else."""
