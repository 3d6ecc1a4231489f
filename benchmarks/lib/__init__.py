"""The yardstick of pathway_tpu's device plane. Nothing here is imported
by the program; only ``lib/system.py`` imports the program."""
