"""Pallas TPU kernels for the paper's compute hot-spots.

kernel            | paper idea            | oracle
------------------|-----------------------|---------------------------
lstm_fxp_seq.py   | C1–C5 fused fxp seq   | ref.lstm_sequence_fxp_ref,
                  | (LSTM and GRU, L >= 1)| ref.gru_sequence_fxp_ref
ssd_scan.py       | C1/C2/C5 for SSD      | ref.ssd_chunk_scan_ref

The fused fixed-point kernel is the served path
(``recurrent_forward(..., backend="pallas_fxp")``); C3 (the shared LUT) and
C4 (the fixed-point ALU) run inside it.  ``ops.py`` dispatches the SSD scan.
Both kernels validate in interpret mode on CPU.
"""

from repro.kernels import ops, ref  # noqa: F401
