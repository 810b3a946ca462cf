"""Plain NumPy references of the stages the cells time.

Nothing here imports the program (``pypulsar_tpu``) or reads a table the
program made: every shift, mask cell and template is derived again from the
raw input file and the configuration. The arithmetic is float64 unless a
caller asks for a lower ``dtype`` (the control that has to fail).
"""
