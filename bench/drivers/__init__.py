"""Traffic drivers: a traffic file names its driver, which sets the cell up,
drives its measured window and checks what the window produced."""
