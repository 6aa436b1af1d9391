"""Paper Fig. 11 demonstration: QAT-train the paper's CIFAR networks and
evaluate them under (a) the ideal bit-true integer model and (b) the full
chip model (BP/BS + ADC) — the claim being that (b) ~= (a).

CIFAR-10 itself is not available offline; a structured synthetic
class-template dataset stands in (the chip-vs-ideal claim is
data-agnostic, DESIGN.md §7).  Reduced topologies by default so this runs
on CPU in a few minutes; pass --full for the exact paper nets.

With ``--noise-sigma S`` (LSB units; try the 0.85 V corner's
``repro.core.adc.SIGMA_LSB_CORNER[0.85]``) training becomes noise-aware
QAT — every forward sees live ADC noise — and after training a BN
calibration pass re-centers the datapath registers under noise before the
noisy evaluation.

Run:  PYTHONPATH=src python examples/train_cifar_qat.py [--net a|b]
      [--steps 60] [--noise-sigma 0.3]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.cifar_nets import NETWORK_A, NETWORK_B
from repro.core import energy as E
from repro.data.pipeline import DataConfig, make_batch
from repro.models.cnn import cnn_forward, cnn_loss, init_cnn, update_bn_stats
from repro.optim import qat
from repro.optim.adamw import AdamWConfig, apply_updates, init_opt_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="a", choices=["a", "b"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--noise-sigma", type=float, default=0.0,
                    help="ADC noise sigma (LSB) for noise-aware QAT + "
                         "calibrated noisy eval; 0 = off")
    args = ap.parse_args()

    net = NETWORK_A if args.net == "a" else NETWORK_B
    if not args.full:
        net = net.reduced()
    data_cfg = DataConfig(kind="cifar_synthetic", global_batch=args.batch,
                          seed=1)
    key = jax.random.PRNGKey(0)
    params = init_cnn(key, net)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=args.steps,
                          weight_decay=0.0)
    opt = init_opt_state(params)

    @jax.jit
    def update(params, opt, batch, noise_key):
        def loss_fn(p):
            if args.noise_sigma:
                # noise-aware QAT: the loss forward sees live ADC noise
                # (the traced key threads through the compiled step)
                with qat.noise_aware(noise_key, args.noise_sigma):
                    return cnn_loss(p, batch, net)
            return cnn_loss(p, batch, net)

        (loss, m), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params, opt, om = apply_updates(params, grads, opt, opt_cfg)
        # maintain the running BN statistics the inference datapath
        # registers are folded from (outside the gradient)
        params = update_bn_stats(params, m.pop("bn_stats"))
        return params, opt, {**m, **om}

    print(f"training {net.name} ({'full' if args.full else 'reduced'}) "
          f"with CIMU QAT (B_A={net.ba}, B_X={net.bx}, {net.readout})")
    t0 = time.time()
    for step in range(args.steps):
        batch = make_batch(data_cfg, step)
        params, opt, m = update(params, opt, batch,
                                jax.random.fold_in(key, step))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"  step {step:4d} loss={float(m['loss']):.3f} "
                  f"acc={float(m['acc']):.3f} ({time.time()-t0:.0f}s)")

    # --- Fig. 11 evaluation: chip model vs ideal bit-true vs float
    eval_batches = [make_batch(data_cfg, 10_000 + i) for i in range(5)]

    def accuracy(backend):
        # inference mode: running BN stats folded into the fused datapath
        # epilogue — logits are batch-composition independent
        accs = []
        for b in eval_batches:
            logits = cnn_forward(params, b["images"], net, backend=backend)
            accs.append(float(jnp.mean(
                (jnp.argmax(logits, -1) == b["labels"]).astype(jnp.float32))))
        return sum(accs) / len(accs)

    acc_chip = accuracy("bpbs")
    acc_ideal = accuracy("digital_int")
    acc_float = accuracy("digital")
    print(f"\naccuracy: chip-model={acc_chip:.3f}  "
          f"ideal-int={acc_ideal:.3f}  float={acc_float:.3f}")

    if args.noise_sigma:
        # 0.85V-corner robustness: calibrate the BN registers under noise,
        # then evaluate with live ADC noise
        def noisy_accuracy(p, k):
            accs = []
            for i, b in enumerate(eval_batches):
                with qat.noise_aware(jax.random.fold_in(k, i),
                                     args.noise_sigma):
                    logits = cnn_forward(p, b["images"], net,
                                         backend="bpbs")
                accs.append(float(jnp.mean((jnp.argmax(logits, -1)
                                            == b["labels"]).astype(
                                                jnp.float32))))
            return sum(accs) / len(accs)

        cal_batches = [make_batch(data_cfg, 20_000 + i) for i in range(4)]
        cal = qat.calibrate_bn_stats(params, cal_batches, net,
                                     jax.random.PRNGKey(7),
                                     args.noise_sigma)
        acc_noisy = noisy_accuracy(params, jax.random.PRNGKey(11))
        acc_cal = noisy_accuracy(cal, jax.random.PRNGKey(11))
        print(f"noisy (sigma={args.noise_sigma} LSB): "
              f"uncalibrated={acc_noisy:.3f}  calibrated={acc_cal:.3f}  "
              f"(noiseless chip: {acc_chip:.3f})")
    print("paper claim: chip ~= ideal "
          f"(A: 92.4 vs 92.7, B: 89.3 vs 89.8) -> gap here: "
          f"{abs(acc_chip - acc_ideal):.3f}")

    cost = (E.network_cost(E.NETWORK_A, 4, 4, vdd=0.85, sparsity=0.5)
            if args.net == "a" else
            E.network_cost(E.NETWORK_B, 1, 1, vdd=0.85, sparsity=0.0,
                           readout="abn", overhead_cycles=149500))
    print(f"chip cost for the full topology: {cost['energy_uj']:.1f} uJ/image"
          f" @ {cost['fps']:.0f} fps "
          f"(paper: {'105.2uJ/23fps' if args.net == 'a' else '5.31uJ/176fps'})")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
