"""The yardstick's own code: nothing here imports the program."""
