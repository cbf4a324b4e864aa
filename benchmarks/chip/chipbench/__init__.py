"""The chip benchmark's yardstick: traffic, reference, trace reduction and the
run harness. Nothing here imports the program except ``harness``, which drives
it; the reference and the reductions stand alone."""
