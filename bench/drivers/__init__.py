"""One module per kind of traffic: each builds the system from a cell,
warms it up, drives the measured window and checks what it produced."""
