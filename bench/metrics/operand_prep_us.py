"""``operand_prep_us.<cells>``: device microseconds per image spent
preparing the CIMA's operands, over every layer: quantizing the input
(``cima.quantize_x``) and the weights (``cima.quantize_w``), the bit
planes and the kernel's operand layout (``cima.planes``), bank and block
padding (``cima.pad``) and the plane-skip liveness bits
(``cima.liveness``).  Read from a short traced window of the cell's own
step joined to its compiled program (``bench/scopes.py``).  Nothing when
the program carries no such scopes or the join places under 90% of the
device seconds."""
from bench import scopes


def read(name, r):
    attr = scopes.window(r)
    return None if attr is None else attr.us_per_image(scopes.OPERAND_PREP)
