"""The benchmark's own machinery: cells found by name, the device check,
FLOP counting, trace reduction and the correctness comparison."""
