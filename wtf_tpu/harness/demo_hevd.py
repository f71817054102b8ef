"""Demo target: an ioctl driver after HackSysExtremeVulnerableDriver, with
the kernel around it that a Windows snapshot brings: LSTAR entry, a real
IDT/GDT/TSS, #PF/#GP/#DE handlers, one `__try` slot a thread.

Role of the reference's HEVD campaign in full (m4drat/wtf
src/wtf/fuzzer_hevd.cc:20-142, the README's HEVD walkthrough, BASELINE.json
config 3).  `demo_kernel` stands in for it with four commands on one byte
and no IDT; here the testcase is upstream's, `u32 IoControlCode | payload`,
the payload placed so that it ENDS at the end of a user page with nothing
mapped behind it (fuzzer_hevd.cc:20-59), and the driver is HEVD's public
ioctl table, CTL_CODE(0x22, 0x800+n, METHOD_NEITHER, FILE_ANY_ACCESS) =
0x222003, 0x222007 ... 0x222037, one handler each, written after the public
source and kept as short as the bug allows:

  0x222003 stack overflow           0x222007 stack overflow, with a cookie
  0x22200B arbitrary write          0x22200F pool overflow
  0x222013 allocate UaF object      0x222017 use UaF object
  0x22201B free UaF object          0x22201F allocate fake object
  0x222023 type confusion           0x222027 integer overflow
  0x22202B null pointer             0x22202F uninitialised stack variable
  0x222033 uninitialised pool       0x222037 double fetch

Every handler runs under `__try/__except`, as HEVD's do: it registers the
except address in a slot of the per-thread page that `gs` reaches after
`swapgs` (ONE slot a thread, where Windows unwinds a chain), works on the
user buffer, a kernel stack buffer or a pool chunk, and unregisters.  A
request whose size field lies, or that is shorter than the structure the
handler reads, runs off the end of the user page: a #PF in ring 0, which
the guest's own handler (gate 14, the KiPageFault analog) turns into
STATUS_ACCESS_VIOLATION from the `__except`.  That is the common end of a
mutated ioctl.  The rare one is a bugcheck: the fault handlers call the
`nt!KeBugCheck2` analog when no `__try` is registered, when the fault is
the instruction fetch itself (a return or a call into wild code), or when
a kernel address faulted (MmAccessFault's 0x50); a handler calls it when it
finds a smashed cookie, a smashed pool header or a chunk freed twice.
`crash_detection.setup_kernel_crash_detection` names the crash by the
bugcheck's code and first argument.  A fault inside the fault path (no
stack left for the frame, or a `gs` that is not the kernel's) cannot be
delivered and stays the lane's terminal status.

Between the LSTAR entry and the driver's dispatcher every request runs an
I/O-manager-shaped path, written after the public shape of
NtDeviceIoControlFile -> IofCallDriver -> IofCompleteRequest for a
METHOD_NEITHER code: the handle is resolved through the process's
two-level handle table to the file object, its type checked and a
reference taken; an IRP is popped from a lookaside list, zeroed whole and
its stack location filled (major function 14, the code, the length,
Type3InputBuffer = the user's pointer, unprobed); IofCallDriver calls
DeviceObject->DriverObject->MajorFunction[14]; on return the status block
goes to the caller's, the IRP back on the list, the reference is dropped,
`sysretq`.  Handle table, objects and IRPs lie in
the pool, with their public x64 layouts.  Plain integer code, about 157
instructions a request with the handler's: the source's path runs tens of
thousands, and benchmark/configs/hevd-fulldump.json says what cut it
(`reduced: request_path_instructions`) and what of NT's it leaves out.

The snapshot is taken in user mode at the `syscall` that enters the
driver, after an object was allocated and freed (`g_UseAfterFreeObject`
dangles).  Buffer sizes are 0x100 where HEVD's are 2 KiB: `--max_len 1028`
cannot overrun 2 KiB.  A `DbgPrint` analog is called once a handler and
`init` returns from it at once by a breakpoint (upstream's
SimulateReturnFromFunction idiom).

Guest layout (kernel pages are supervisor-only):
  user   0x14000000   syscall ; nop (finish) ; hlt
         0x20000000   the input page, payload at its end, guard behind
         0x7FFE0000   a page of user data, where well-formed pointers point
         0x7FFC0000   a page with a user-built object at its end
  kernel 0xFFFFF80000200000  the kernel's and the driver's code (LSTAR), two
         pages of stack between guards, the per-thread page, IDT, GDT, TSS
  pool   POOL_PAGES, `pool` of `lay_out`: here at 0xFFFF800000400000 and
         on; in a dump wherever the direct map has their frames

Testcase ABI (insert_testcase): r10 = IoControlCode, rsi = buffer, rdx =
length; the snapshot's own r8 = the device's handle, r9 = the caller's
IO_STATUS_BLOCK on its stack.  Assembled with binutils (Intel syntax, `gs`
as a prefix); bytes embedded, source in _KERN_ASM (tests/test_hevd.py
re-assembles it).

`demo_hevd_dump` is this guest inside a 4-GiB full dump (configuration
`hevd-fulldump`, cell `hevd-faults`).
"""

from __future__ import annotations

import struct
from typing import List, Sequence

from wtf_tpu.core.cpustate import GlobalSeg, Seg
from wtf_tpu.core.results import Ok
from wtf_tpu.harness import crash_detection
from wtf_tpu.harness.targets import Target
from wtf_tpu.snapshot.loader import Snapshot
from wtf_tpu.snapshot.synthetic import SyntheticSnapshotBuilder

USER_CODE = 0x0000_1400_0000
FINISH_GVA = USER_CODE + 2              # the nop after syscall
USER_BUF = 0x0000_2000_0000             # one page; the next is not mapped
USER_BUF_END = USER_BUF + 0x1000
USER_DATA = 0x0000_7FFE_0000            # where well-formed pointers point
USER_STACK_TOP = 0x0000_7FFD_1000
MAX_PAYLOAD = 0x1000

KERN_CODE = 0xFFFF_F800_0020_0000
KSTACK = 0xFFFF_F800_0021_0000          # two pages between guards
KSTACK_TOP = KSTACK + 0x2000 - 0x40     # gs:[8] and TSS.RSP0
KPCR = 0xFFFF_F800_0022_0000            # kernel_gs_base: the per-thread page
KIDT = 0xFFFF_F800_0023_0000
KGDT = 0xFFFF_F800_0023_1000
KTSS = 0xFFFF_F800_0023_2000
POOL = 0xFFFF_8000_0040_0000            # this target's pool pages, a guard
POOL_STRIDE = 0x2000                    #   behind each
# the pool's pages, in the order `lay_out` and `pool_pages` take them
POOL_PAGES = ("chunk", "freed", "fresh", "handles", "entries", "objects",
              "irps")

# labels of _KERN_ASM (tests/test_hevd.py checks the bytes at each)
K_EXIT = KERN_CODE + 0x1D               # the system call's way out
K_IO_CONTROL = KERN_CODE + 0x2F         # NtDeviceIoControlFile
K_CALL_DRIVER = KERN_CODE + 0x177       # IofCallDriver
K_DISPATCH = KERN_CODE + 0x1AE          # the driver's MajorFunction[14]
K_COMPLETE = KERN_CODE + 0x1DC          # where a handler returns to
K_EXCEPT = KERN_CODE + 0x20F
K_DBGPRINT = KERN_CODE + 0x233
K_CALLBACK = KERN_CODE + 0x235
K_PF = KERN_CODE + 0x597
K_GP = KERN_CODE + 0x5FE
K_DE = KERN_CODE + 0x63B
K_BUGCHECK = KERN_CODE + 0x68D
K_TABLE = KERN_CODE + 0x690             # the fourteen handlers' addresses

# the per-thread page, by offset: what the snapshot sets; the code also
# keeps the user's rsp at 0, the __try's except address and rsp at 0x10
# and 0x18, the status the fault path writes at 0x20
PCR_KSTACK = 0x08
PCR_UAF_OBJECT, PCR_POOL_CHUNK, PCR_FRESH_CHUNK, PCR_COOKIE, PCR_OBJECT = \
    0x28, 0x30, 0x38, 0x40, 0x48
PCR_HANDLE_TABLE, PCR_IRP_LOOKASIDE = 0x50, 0x58

# the pool pages' contents
BUFFER_SIZE = 0x100                     # every kernel buffer and chunk
POOL_CHUNK_OFF = 0x100                  # pool overflow's chunk
POOL_HEADER = 0x6B63614844564548        # b"HEVDHack": the next chunk's header
UAF_OBJECT_OFF = 0x208                  # the freed object, header before it
FREED_TAG = 0x65657246                  # b"Free"
FREED_FILL = 0xDEADDEADDEADDEAD
STALE_OFF = 0x100                       # uninitialised pool's chunk
OBJECT_OFF = 0x200                      # an object: { table }, and its table
USER_OBJECT = 0x0000_7FFC_0FE0          # the same, as a user would build
#                                         it, at the end of a page of its own
STACK_COOKIE = 0x2B992DDFA232

# what the I/O path walks, after the public layouts (x64): the process's
# two-level handle table, the file, device and driver objects, the IRPs
FILE_HANDLE = 0x1D4                     # r8 at the syscall
IOSB = USER_STACK_TOP - 0x40            # r9: the caller's IO_STATUS_BLOCK
HANDLE_TABLE_OFF = 0x40                 # HANDLE_TABLE { TableCode, next }
HANDLE_MIDDLE_OFF = 0x800               # its middle level: low-level pages
HANDLES_A_PAGE = 256                    # HANDLE_TABLE_ENTRY is 16 bytes
OBJECT_HEADER_SIZE = 0x30               # PointerCount first, the body behind
FILE_OBJECT_OFF = 0x100 + OBJECT_HEADER_SIZE
DEVICE_OBJECT_OFF = 0x300
DRIVER_OBJECT_OFF = 0x500
IO_TYPE_DEVICE, IO_TYPE_DRIVER, IO_TYPE_FILE, IO_TYPE_IRP = 3, 4, 5, 6
IRP_MJ_DEVICE_CONTROL = 14
MAJOR_FUNCTION_OFF = 0x70               # DRIVER_OBJECT.MajorFunction[28]
IRP_SIZE = 0xD0 + 0x48                  # an IRP and its one stack location
IRP_STRIDE = 0x120                      # what IoInitializeIrp zeroes
IRPS_OFF = 0x40                         # behind the lookaside list's header
N_IRPS = 4
IRP_STATUS_OFF, IRP_USER_IOSB_OFF, IRP_STACK_OFF = 0x30, 0x48, 0xD0

MAGIC = 0xBAD0B0B0
IOCTLS = tuple(0x222003 + 4 * n for n in range(14))
(IOCTL_STACK_OVERFLOW, IOCTL_STACK_OVERFLOW_GS, IOCTL_ARBITRARY_WRITE,
 IOCTL_POOL_OVERFLOW, IOCTL_ALLOCATE_UAF, IOCTL_USE_UAF, IOCTL_FREE_UAF,
 IOCTL_ALLOCATE_FAKE, IOCTL_TYPE_CONFUSION, IOCTL_INTEGER_OVERFLOW,
 IOCTL_NULL_POINTER, IOCTL_UNINIT_STACK, IOCTL_UNINIT_POOL,
 IOCTL_DOUBLE_FETCH) = IOCTLS

STATUS_SUCCESS = 0
STATUS_UNSUCCESSFUL = 0xC0000001
STATUS_ACCESS_VIOLATION = 0xC0000005
STATUS_INVALID_DEVICE_REQUEST = 0xC0000010
STATUS_INVALID_BUFFER_SIZE = 0xC0000206

_USER_ASM = "syscall ; nop ; hlt"
_USER_CODE = bytes.fromhex("0f0590f4")

_KERN_ASM = """
    .set KERN, 0xFFFFF80000200000
k_entry:                            # IA32_LSTAR: KiSystemCall64
    swapgs
    gs mov [0x00], rsp              # user rsp
    gs mov rsp, [0x08]              # this thread's kernel stack
    push rcx                        # user rip
    push r11                        # user rflags
    call k_nt_device_io_control     # r8 = handle, r9 = status block, r10 =
k_exit:                             #   code, rsi = buffer, rdx = length
    pop r11                         # KiSystemServiceExit
    pop rcx
    gs mov rsp, [0x00]
    swapgs
    sysretq

# The path keeps its state in r8-r11, which no handler touches: r8 the
# file object, r9 the caller's status block, r10 the code and then the
# stack location, r11 the IRP.
k_nt_device_io_control:             # NtDeviceIoControlFile -> IopXxxControlFile
    mov rax, r8                     # ObReferenceObjectByHandle:
    test al, 3                      #   ExpLookupHandleTableEntry
    jnz k_bad_handle
    shr rax, 2                      # the handle's index
    gs mov rbx, [0x50]              # the process's HANDLE_TABLE
    cmp rax, [rbx + 8]              # NextHandleNeedingPool
    jae k_bad_handle
    mov rcx, [rbx]                  # TableCode: the level in its low bits
    and rcx, -4
    mov rdi, rax
    shr rdi, 8                      # 256 entries a page
    mov rcx, [rcx + rdi*8]          # the low-level page
    test rcx, rcx
    jz k_bad_handle
    movzx eax, al
    shl eax, 4                      # HANDLE_TABLE_ENTRY: 16 bytes
    mov rax, [rcx + rax]            # the OBJECT_HEADER | attributes
    and rax, -8
    jz k_bad_handle
    lea r8, [rax + 0x30]            # the body: a FILE_OBJECT
    cmp word ptr [r8], 5            # IO_TYPE_FILE
    jne k_bad_type
    inc qword ptr [r8 - 0x30]       # PointerCount
    gs mov rbx, [0x58]              # IoAllocateIrp: the lookaside list
    mov r11, [rbx]                  # ExInterlockedPopEntrySList
    test r11, r11
    jz k_no_irp
    mov rax, [r11]
    mov [rbx], rax
    mov rdi, r11                    # IoInitializeIrp: zero the IRP and its
    xor eax, eax                    #   stack location, 0x120 bytes, twelve
    mov ecx, 3                      #   qwords a turn
1:  mov [rdi], rax
    mov [rdi + 0x08], rax
    mov [rdi + 0x10], rax
    mov [rdi + 0x18], rax
    mov [rdi + 0x20], rax
    mov [rdi + 0x28], rax
    mov [rdi + 0x30], rax
    mov [rdi + 0x38], rax
    mov [rdi + 0x40], rax
    mov [rdi + 0x48], rax
    mov [rdi + 0x50], rax
    mov [rdi + 0x58], rax
    add rdi, 0x60
    dec ecx
    jnz 1b
    mov word ptr [r11], 6           # IO_TYPE_IRP
    mov word ptr [r11 + 2], 0x118   # Size
    mov word ptr [r11 + 0x42], 0x0201   # StackCount 1, CurrentLocation 2
    lea rax, [r11 + 0x118]
    mov [r11 + 0xB8], rax           # CurrentStackLocation: one past the last
    mov [r11 + 0x48], r9            # UserIosb
    lea rdi, [r11 + 0xD0]           # IoGetNextIrpStackLocation
    mov byte ptr [rdi], 14          # IRP_MJ_DEVICE_CONTROL
    mov [rdi + 0x18], r10d          # Parameters.DeviceIoControl.IoControlCode
    mov [rdi + 0x10], edx           # .InputBufferLength
    mov [rdi + 0x20], rsi           # .Type3InputBuffer: METHOD_NEITHER hands
    mov [rdi + 0x30], r8            #   the user's pointer on, unprobed
    call k_iof_call_driver
    mov rbx, [r11 + 0x48]           # IopCompleteRequest: the status block
    mov rax, [r11 + 0x30]           #   goes to the caller's
    mov [rbx], rax
    mov rax, [r11 + 0x38]
    mov [rbx + 8], rax
    gs mov rbx, [0x58]              # IoFreeIrp: back on the lookaside list
    mov rax, [rbx]
    mov [r11], rax
    mov [rbx], r11
    dec qword ptr [r8 - 0x30]       # ObDereferenceObject
    mov eax, [r11 + 0x30]
    ret
k_bad_handle:
    mov eax, 0xC0000008             # STATUS_INVALID_HANDLE
    ret
k_bad_type:
    mov eax, 0xC0000024             # STATUS_OBJECT_TYPE_MISMATCH
    ret
k_no_irp:
    dec qword ptr [r8 - 0x30]
    mov eax, 0xC000009A             # STATUS_INSUFFICIENT_RESOURCES
    ret
k_iof_call_driver:                  # IofCallDriver(r8 = file, r11 = IRP)
    dec byte ptr [r11 + 0x43]       # CurrentLocation
    jle 1f
    sub qword ptr [r11 + 0xB8], 0x48
    mov r10, [r11 + 0xB8]           # IoSetNextIrpStackLocation
    mov rbx, [r8 + 8]               # IoGetRelatedDeviceObject
    mov [r10 + 0x28], rbx           # the location's DeviceObject
    movzx eax, byte ptr [r10]       # MajorFunction
    mov rbx, [rbx + 8]              # DeviceObject->DriverObject
    call qword ptr [rbx + rax*8 + 0x70]     # ->MajorFunction[14]
    ret
1:  mov ecx, 0x35                   # NO_MORE_IRP_STACK_LOCATIONS
    mov rdx, r11
    jmp k_bugcheck

k_dispatch:                         # hevd!IrpDeviceIoCtlHandler(r11 = IRP)
    mov r10, [r11 + 0xB8]           # IoGetCurrentIrpStackLocation
    mov eax, [r10 + 0x18]           # IoControlCode
    mov rsi, [r10 + 0x20]           # Type3InputBuffer
    mov edx, [r10 + 0x10]           # InputBufferLength
    sub eax, 0x222003               # CTL_CODE(0x22, 0x800+n, NEITHER, ANY)
    test al, 3
    jnz k_invalid
    shr eax, 2
    cmp eax, 14
    jae k_invalid
    lea rbx, [rip + k_table]        # the switch
    call qword ptr [rbx + rax*8]    # handler(rsi = buffer, rdx = length)
k_complete:
    mov [r11 + 0x30], eax           # Irp->IoStatus.Status
    mov qword ptr [r11 + 0x38], 0   # .Information
    ret                             # IofCompleteRequest: see IopCompleteRequest
k_invalid:
    mov eax, 0xC0000010             # STATUS_INVALID_DEVICE_REQUEST
    jmp k_complete
k_try:                              # __try: ONE except slot a thread
    lea rax, [rip + k_except]
    gs mov [0x10], rax
    lea rax, [rsp + 8]              # the handler's rsp at its entry
    gs mov [0x18], rax
    ret
k_except:                           # __except: the fault path lands here
    gs mov eax, [0x20]              # the status it wrote
k_done:                             # a handler's way out, eax = status
    gs mov qword ptr [0x10], 0
    ret
k_bad_size:
    mov eax, 0xC0000206             # STATUS_INVALID_BUFFER_SIZE
    jmp k_done
k_unsuccessful:
    mov eax, 0xC0000001             # STATUS_UNSUCCESSFUL
    jmp k_done
k_dbgprint:                         # DbgPrint: init returns from it at once
    nop
    ret
k_callback:                         # an object's rightful callback
    ret
k_memcpy:                           # rdi = dst, rsi = src, rcx = bytes
    mov rax, rcx
    shr rcx, 3
    rep movsq
    mov rcx, rax
    and ecx, 7
    rep movsb
    ret

h_stack_overflow:                   # 0x222003: { u32 Size, data }
    call k_try
    call k_dbgprint
    sub rsp, 0x100                  # KernelBuffer
    mov rdi, rsp
    mov ecx, [rsi]
    add rsi, 4
    call k_memcpy                   # BUG: Size is the caller's
    add rsp, 0x100
    xor eax, eax
    jmp k_done
h_stack_overflow_gs:                # 0x222007: { u32 Size, data }
    call k_try
    call k_dbgprint
    gs push qword ptr [0x40]        # the cookie under the return address
    sub rsp, 0x100
    mov rdi, rsp
    mov ecx, [rsi]
    add rsi, 4
    call k_memcpy                   # BUG
    add rsp, 0x100
    pop rdx
    gs cmp rdx, [0x40]
    jne 1f
    xor eax, eax
    jmp k_done
1:  mov ecx, 0xF7                   # DRIVER_OVERRAN_STACK_BUFFER
    jmp k_bugcheck
h_arbitrary_write:                  # 0x22200B: { What, Where }
    call k_try
    call k_dbgprint
    mov rax, [rsi]
    mov rbx, [rsi + 8]
    mov rax, [rax]
    mov [rbx], rax                  # BUG: *Where = *What
    xor eax, eax
    jmp k_done
h_pool_overflow:                    # 0x22200F: { u32 Size, data }
    call k_try
    call k_dbgprint
    gs mov rdi, [0x30]              # a 0x100-byte chunk of the pool
    mov rbx, rdi
    mov ecx, [rsi]
    add rsi, 4
    call k_memcpy                   # BUG
    mov rax, 0x6b63614844564548     # the next chunk's header
    cmp [rbx + 0x100], rax          # ExFreePool looks at it
    jne 1f
    xor eax, eax
    jmp k_done
1:  mov ecx, 0x19                   # BAD_POOL_HEADER
    mov edx, 0x20
    jmp k_bugcheck
h_allocate_uaf:                     # 0x222013
    call k_try
    call k_dbgprint
    gs mov rbx, [0x38]              # a fresh chunk
    lea rax, [rip + k_callback]
    mov [rbx], rax
    lea rdi, [rbx + 8]
    mov rax, 0x4141414141414141
    mov ecx, 6
    rep stosq
    gs mov [0x28], rbx              # g_UseAfterFreeObject
    xor eax, eax
    jmp k_done
h_use_uaf:                          # 0x222017
    call k_try
    call k_dbgprint
    gs mov rbx, [0x28]
    test rbx, rbx
    jz k_unsuccessful
    mov rax, [rbx]
    test rax, rax
    jz k_unsuccessful
    call rax                        # BUG: the object may be freed
    xor eax, eax
    jmp k_done
h_free_uaf:                         # 0x22201B
    call k_try
    call k_dbgprint
    gs mov rdi, [0x28]
    test rdi, rdi
    jz k_unsuccessful
    cmp dword ptr [rdi - 8], 0x65657246     # the chunk's header: 'Free'
    je 1f
    mov dword ptr [rdi - 8], 0x65657246
    mov rax, 0xDEADDEADDEADDEAD
    mov ecx, 7
    rep stosq                       # BUG: g_UseAfterFreeObject stays
    xor eax, eax
    jmp k_done
1:  mov ecx, 0xC2                   # BAD_POOL_CALLER: freed twice
    mov edx, 7
    jmp k_bugcheck
h_allocate_fake:                    # 0x22201F: a 0x38-byte object
    call k_try
    call k_dbgprint
    gs mov rdi, [0x28]              # the allocator hands the freed chunk out
    mov ecx, 0x38
    call k_memcpy
    xor eax, eax
    jmp k_done
h_type_confusion:                   # 0x222023: { ObjectID, ObjectType }
    call k_try
    call k_dbgprint
    mov rax, [rsi + 8]
    mov rbx, [rsi]
    push rax                        # kernel object { ObjectID, ObjectType }:
    push rbx                        #   the type shares its slot with Object
    mov rax, [rsp + 8]              # BUG: the initializer takes the type
    mov rax, [rax]                  #   for the object, and calls through
    call qword ptr [rax + 8]        #   its table
    add rsp, 16
    xor eax, eax
    jmp k_done
h_integer_overflow:                 # 0x222027: { u32 Size, data }
    call k_try
    call k_dbgprint
    mov eax, [rsi]
    lea ebx, [eax + 4]              # BUG: Size + terminator wraps
    cmp ebx, 0x100
    ja k_bad_size
    sub rsp, 0x100
    mov ecx, eax
    shr ecx, 2
    lea rsi, [rsi + 4]
    mov rdi, rsp
1:  test ecx, ecx
    jz 2f
    mov eax, [rsi]
    cmp eax, 0xBAD0B0B0             # the terminator
    je 2f
    mov [rdi], eax
    add rsi, 4
    add rdi, 4
    dec ecx
    jmp 1b
2:  add rsp, 0x100
    xor eax, eax
    jmp k_done
h_null_pointer:                     # 0x22202B: { u32 magic }
    call k_try
    call k_dbgprint
    xor ebx, ebx
    cmp dword ptr [rsi], 0xBAD0B0B0
    jne 1f
    gs mov rbx, [0x48]              # the object, allocated
1:  mov rax, [rbx]                  # BUG: or NULL
    call qword ptr [rax + 8]
    xor eax, eax
    jmp k_done
h_uninit_stack:                     # 0x22202F: { u32 magic, u64 }
    call k_try
    call k_dbgprint
    mov rax, [rsi + 4]              # an earlier frame's local: stale on the
    push rax                        #   stack when it returns
    pop rax
    sub rsp, 16                     # { Value, Object }, not initialised
    cmp dword ptr [rsi], 0xBAD0B0B0
    jne 1f
    mov qword ptr [rsp], 0x41
    gs mov rax, [0x48]
    mov [rsp + 8], rax
1:  mov rax, [rsp + 8]              # BUG
    test rax, rax
    jz 2f
    mov rax, [rax]
    call qword ptr [rax + 8]
2:  add rsp, 16
    xor eax, eax
    jmp k_done
h_uninit_pool:                      # 0x222033: { u32 magic }
    call k_try
    call k_dbgprint
    gs mov rbx, [0x38]              # a chunk whose last owner's bytes stand
    cmp dword ptr [rsi], 0xBAD0B0B0
    jne 1f
    mov qword ptr [rbx + 0x100], 0x41
    gs mov rax, [0x48]
    mov [rbx + 0x108], rax
1:  mov rax, [rbx + 0x108]          # BUG
    test rax, rax
    jz 2f
    mov rax, [rax]
    call qword ptr [rax + 8]
2:  xor eax, eax
    jmp k_done
h_double_fetch:                     # 0x222037: { Buffer, Size }
    call k_try
    call k_dbgprint
    mov rax, [rsi + 8]              # the first fetch of Size
    cmp rax, 0x100
    ja k_bad_size
    sub rsp, 0x100
    mov rcx, [rsi + 8]              # BUG: fetched again
    mov rsi, [rsi]
    mov rdi, rsp
    call k_memcpy
    add rsp, 0x100
    xor eax, eax
    jmp k_done

k_pf:                               # IDT gate 14: KiPageFault
    test byte ptr [rsp + 16], 3     # from ring 3: its gs is the user's
    jz 1f
    swapgs
1:  push rax
    push rbx
    mov rax, cr2
    cmp rax, [rsp + 24]             # the fetch itself: nothing to resume
    je 3f
    test rax, rax
    js 3f                           # a kernel address: MmAccessFault's 0x50
    gs mov rbx, [0x10]
    test rbx, rbx
    jz 3f                           # no __try
2:  mov [rsp + 24], rbx             # the frame's rip: the __except
    gs mov rbx, [0x18]
    mov [rsp + 48], rbx             # the frame's rsp: as at the __try
    gs mov dword ptr [0x20], 0xC0000005
    pop rbx
    pop rax
    add rsp, 8                      # the error code
    iretq
3:  mov ecx, 0x50                   # PAGE_FAULT_IN_NONPAGED_AREA
    mov rdx, rax
    mov r8, [rsp + 16]
    mov r9, [rsp + 24]
    jmp k_bugcheck
k_gp:                               # IDT gate 13
    test byte ptr [rsp + 16], 3
    jz 1f
    swapgs
1:  push rax
    push rbx
    mov rax, [rsp + 24]
    sar rax, 47                     # a wild transfer: rip is not canonical
    inc rax
    cmp rax, 1
    ja 4f
    gs mov rbx, [0x10]
    test rbx, rbx
    jnz 2b
4:  mov ecx, 0x1E                   # KMODE_EXCEPTION_NOT_HANDLED
    mov edx, 0xC0000005
    mov r8, [rsp + 24]
    jmp k_bugcheck
k_de:                               # IDT gate 0: no error code
    test byte ptr [rsp + 8], 3
    jz 1f
    swapgs
1:  push 0                          # the frame as the other two have it
    push rax
    push rbx
    gs mov rbx, [0x10]
    test rbx, rbx
    jz 5f
    mov [rsp + 24], rbx
    gs mov rbx, [0x18]
    mov [rsp + 48], rbx
    gs mov dword ptr [0x20], 0xC0000094
    pop rbx
    pop rax
    add rsp, 8
    iretq
5:  mov ecx, 0x1E
    mov edx, 0xC0000094
    mov r8, [rsp + 24]
k_bugcheck:                         # nt!KeBugCheck2: rcx = code, rdx ...
    nop
    hlt
    .balign 8
k_table:
    .quad KERN + h_stack_overflow - k_entry
    .quad KERN + h_stack_overflow_gs - k_entry
    .quad KERN + h_arbitrary_write - k_entry
    .quad KERN + h_pool_overflow - k_entry
    .quad KERN + h_allocate_uaf - k_entry
    .quad KERN + h_use_uaf - k_entry
    .quad KERN + h_free_uaf - k_entry
    .quad KERN + h_allocate_fake - k_entry
    .quad KERN + h_type_confusion - k_entry
    .quad KERN + h_integer_overflow - k_entry
    .quad KERN + h_null_pointer - k_entry
    .quad KERN + h_uninit_stack - k_entry
    .quad KERN + h_uninit_pool - k_entry
    .quad KERN + h_double_fetch - k_entry
"""

_KERN_CODE = bytes.fromhex(
    "0f01f865488924250000000065488b242508000000514153e812000000415b59"
    "65488b2425000000000f01f8480f074c89c0a8030f852701000048c1e8026548"
    "8b1c2550000000483b43080f8310010000488b0b4883e1fc4889c748c1ef0848"
    "8b0cf94885c90f84f50000000fb6c0c1e004488b04014883e0f80f84e1000000"
    "4c8d403066418338050f85d800000049ff40d065488b1c25580000004c8b1b4d"
    "85db0f84c5000000498b034889034c89df31c0b9030000004889074889470848"
    "8947104889471848894720488947284889473048894738488947404889474848"
    "894750488947584883c760ffc975c96641c70306006641c7430218016641c743"
    "420102498d8318010000498983b80000004d894b48498dbbd0000000c6070e44"
    "895718895710488977204c894730e844000000498b5b48498b4330488903498b"
    "43384889430865488b1c2558000000488b034989034c891b49ff48d0418b4330"
    "c3b8080000c0c3b8240000c0c349ff48d0b89a0000c0c341fe4b437e244983ab"
    "b8000000484d8b93b8000000498b580849895a28410fb602488b5b08ff54c370"
    "c3b9350000004c89dae9df0400004d8b93b8000000418b4218498b7220418b52"
    "102d03202200a803751fc1e80283f80e7317488d1db7040000ff14c341894330"
    "49c7433800000000c3b8100000c0ebec488d0518000000654889042510000000"
    "488d442408654889042518000000c3658b0425200000006548c7042510000000"
    "00000000c3b8060200c0ebebb8010000c0ebe490c3c34889c848c1e903f348a5"
    "4889c183e107f3a4c3e8a2ffffffe8e0ffffff4881ec000100004889e78b0e48"
    "83c604e8ceffffff4881c40001000031c0eba4e878ffffffe8b6ffffff65ff34"
    "25400000004881ec000100004889e78b0e4883c604e89cffffff4881c4000100"
    "005a65483b142540000000750731c0e963ffffffb9f7000000e9cf030000e82d"
    "ffffffe86bffffff488b06488b5e08488b0048890331c0e93bffffffe80fffff"
    "ffe84dffffff65488b3c25300000004889fb8b0e4883c604e839ffffff48b848"
    "4556444861636b48398300010000750731c0e900ffffffb919000000ba200000"
    "00e967030000e8c5feffffe803ffffff65488b1c2538000000488d05f5feffff"
    "488903488d7b0848b84141414141414141b906000000f348ab6548891c252800"
    "000031c0e9aefeffffe882feffffe8c0feffff65488b1c25280000004885db0f"
    "84a7feffff488b034885c00f849bfeffffffd031c0e97dfeffffe851feffffe8"
    "8ffeffff65488b3c25280000004885ff0f8476feffff817ff8467265657420c7"
    "47f84672656548b8addeaddeaddeaddeb907000000f348ab31c0e938feffffb9"
    "c2000000ba07000000e99f020000e8fdfdffffe83bfeffff65488b3c25280000"
    "00b938000000e82bfeffff31c0e905feffffe8d9fdffffe817feffff488b4608"
    "488b1e5053488b442408488b00ff50084883c41031c0e9dcfdffffe8b0fdffff"
    "e8eefdffff8b06678d580481fb000100000f87cefdffff4881ec0001000089c1"
    "c1e902488d76044889e785c974178b063db0b0d0ba740e89074883c6044883c7"
    "04ffc9ebe54881c40001000031c0e984fdffffe858fdffffe896fdffff31db81"
    "3eb0b0d0ba750965488b1c2548000000488b03ff500831c0e95afdffffe82efd"
    "ffffe86cfdffff488b460450584883ec10813eb0b0d0ba751648c70424410000"
    "0065488b0425480000004889442408488b4424084885c07406488b00ff500848"
    "83c41031c0e90dfdffffe8e1fcffffe81ffdffff65488b1c2538000000813eb0"
    "b0d0ba751b48c783000100004100000065488b04254800000048898308010000"
    "488b83080100004885c07406488b00ff500831c0e9befcffffe892fcffffe8d0"
    "fcffff488b4608483d000100000f87b2fcffff4881ec00010000488b4e08488b"
    "364889e7e8adfcffff4881c40001000031c0e980fcfffff64424100374030f01"
    "f850530f20d0483b442418743a4885c0783565488b1c25100000004885db7427"
    "48895c241865488b1c251800000048895c243065c7042520000000050000c05b"
    "584883c40848cfb9500000004889c24c8b4424104c8b4c2418e98f000000f644"
    "24100374030f01f85053488b44241848c1f82f48ffc04883f801770e65488b1c"
    "25100000004885db7596b91e000000ba050000c04c8b442418eb52f644240803"
    "74030f01f86a00505365488b1c25100000004885db742748895c241865488b1c"
    "251800000048895c243065c7042520000000940000c05b584883c40848cfb91e"
    "000000ba940000c04c8b44241890f4904902200000f8ffff7302200000f8ffff"
    "be02200000f8ffffdc02200000f8ffff2603200000f8ffff6903200000f8ffff"
    "9a03200000f8ffffee03200000f8ffff1204200000f8ffff3b04200000f8ffff"
    "9304200000f8ffffbd04200000f8ffff0a05200000f8ffff5905200000f8ffff"
)


def _gate(handler: int, selector: int = 0x10) -> bytes:
    """One 16-byte long-mode interrupt gate, present, DPL 0, no IST (SDM
    Vol 3A 6.14.1)."""
    return struct.pack("<HHBBHII", handler & 0xFFFF, selector, 0, 0x8E,
                       (handler >> 16) & 0xFFFF, handler >> 32, 0)


def _gdt() -> bytes:
    """Windows' x64 selectors: 0x10 kernel code, 0x18 kernel data, 0x20
    user code (compat), 0x28 user data, 0x30 user code, 0x40 the TSS."""
    flat = {0x10: 0x00209B0000000000, 0x18: 0x00CF93000000FFFF,
            0x20: 0x00CFFB000000FFFF, 0x28: 0x00CFF3000000FFFF,
            0x30: 0x0020FB0000000000}
    gdt = bytearray(0x50)
    for selector, descriptor in flat.items():
        struct.pack_into("<Q", gdt, selector, descriptor)
    base, limit = KTSS, 0x67
    struct.pack_into(
        "<QQ", gdt, 0x40,
        (limit & 0xFFFF) | ((base & 0xFFFFFF) << 16) | (0x8B << 40)
        | (((base >> 24) & 0xFF) << 56), base >> 32)
    return bytes(gdt)


def _object(at: int) -> bytes:
    """An object at `at`: a pointer to its table, and the table behind
    it, whose second slot is the callback."""
    return struct.pack("<QQQQ", at + 0x10, 0, 0, K_CALLBACK)


def pool_pages(pool: Sequence[int]) -> List[bytes]:
    """The pool pages at the addresses `pool` (POOL_PAGES' order), as the
    snapshot finds them.  HEVD's: the chunk pool overflow fills with the
    next chunk's header behind it; the UaF object, freed; a page of fresh
    chunks, one with its last owner's bytes, and the driver's rightful
    object.  The I/O manager's: the process's handle table and its middle
    level; the low-level page that holds FILE_HANDLE's entry; the file
    object the handle names, its device and the driver object whose
    MajorFunction[14] is the dispatcher; the IRP lookaside list."""
    chunk, freed, fresh, handles, entries, objects, irps = (
        bytearray(0x1000) for _ in POOL_PAGES)
    at = dict(zip(POOL_PAGES, pool))
    index = FILE_HANDLE >> 2
    struct.pack_into("<QQ", handles, HANDLE_TABLE_OFF,
                     (at["handles"] + HANDLE_MIDDLE_OFF) | 1,
                     HANDLES_A_PAGE)
    struct.pack_into("<Q", handles, HANDLE_MIDDLE_OFF
                     + 8 * (index // HANDLES_A_PAGE), at["entries"])
    file_object = at["objects"] + FILE_OBJECT_OFF
    struct.pack_into("<QQ", entries, 16 * (index % HANDLES_A_PAGE),
                     (file_object - OBJECT_HEADER_SIZE) | 1, 0x12019F)
    struct.pack_into("<QQ", objects, FILE_OBJECT_OFF - OBJECT_HEADER_SIZE,
                     1, 1)              # PointerCount, HandleCount
    struct.pack_into("<HHIQ", objects, FILE_OBJECT_OFF, IO_TYPE_FILE, 0xD8,
                     0, at["objects"] + DEVICE_OBJECT_OFF)
    struct.pack_into("<HHIQ", objects, DEVICE_OBJECT_OFF, IO_TYPE_DEVICE,
                     0x150, 1, at["objects"] + DRIVER_OBJECT_OFF)
    objects[DEVICE_OBJECT_OFF + 0x4C] = 1               # StackSize
    struct.pack_into("<HHIQ", objects, DRIVER_OBJECT_OFF, IO_TYPE_DRIVER,
                     0x150, 0, at["objects"] + DEVICE_OBJECT_OFF)
    struct.pack_into("<Q", objects, DRIVER_OBJECT_OFF + MAJOR_FUNCTION_OFF
                     + 8 * IRP_MJ_DEVICE_CONTROL, K_DISPATCH)
    free = [at["irps"] + IRPS_OFF + i * IRP_STRIDE for i in range(N_IRPS)]
    struct.pack_into("<QI", irps, 0, free[0], N_IRPS)    # the list's head
    for irp, following in zip(free, free[1:] + [0]):
        struct.pack_into("<Q", irps, irp - at["irps"], following)
    struct.pack_into("<Q", chunk, POOL_CHUNK_OFF + BUFFER_SIZE, POOL_HEADER)
    struct.pack_into("<I", freed, UAF_OBJECT_OFF - 8, FREED_TAG)
    struct.pack_into("<7Q", freed, UAF_OBJECT_OFF, *[FREED_FILL] * 7)
    struct.pack_into("<QQ", fresh, STALE_OFF, 0x4242424242424242,
                     FREED_FILL)
    fresh[OBJECT_OFF:OBJECT_OFF + 0x20] = _object(at["fresh"] + OBJECT_OFF)
    return [bytes(page) for page in (chunk, freed, fresh, handles, entries,
                                     objects, irps)]


def lay_out(b: SyntheticSnapshotBuilder, pool: Sequence[int]):
    """The guest in `b`'s frames -> (pages, cpu), stopped in user mode at
    the `syscall`.  `pool` holds the addresses of the pool's pages
    (POOL_PAGES' order), which the caller maps and fills with
    `pool_pages(pool)` (demo_hevd_dump reaches them through a dump's
    direct map)."""
    int3 = b"\xcc" * 0x1000
    b.write(USER_CODE, _USER_CODE + int3[len(_USER_CODE):])
    b.write(USER_BUF, int3)
    b.write(USER_DATA, int3)
    b.write(USER_OBJECT & ~0xFFF, int3)
    b.write(USER_OBJECT, _object(USER_OBJECT))
    b.map(USER_STACK_TOP - 0x1000, 0x1000)
    b.write(KERN_CODE, _KERN_CODE, user=False)
    b.map(KSTACK, 0x2000, user=False)

    pcr = bytearray(0x1000)
    chunk, freed, fresh, handles, _, _, irps = pool
    for off, value in ((PCR_KSTACK, KSTACK_TOP),
                       (PCR_HANDLE_TABLE, handles + HANDLE_TABLE_OFF),
                       (PCR_IRP_LOOKASIDE, irps),
                       (PCR_UAF_OBJECT, freed + UAF_OBJECT_OFF),
                       (PCR_POOL_CHUNK, chunk + POOL_CHUNK_OFF),
                       (PCR_FRESH_CHUNK, fresh),
                       (PCR_COOKIE, STACK_COOKIE),
                       (PCR_OBJECT, fresh + OBJECT_OFF)):
        struct.pack_into("<Q", pcr, off, value)
    b.write(KPCR, bytes(pcr), user=False)

    idt = bytearray(0x1000)
    for vector, handler in ((0, K_DE), (13, K_GP), (14, K_PF)):
        idt[vector * 16:vector * 16 + 16] = _gate(handler)
    b.write(KIDT, bytes(idt), user=False)
    b.write(KGDT, _gdt(), user=False)
    tss = bytearray(0x68)
    struct.pack_into("<Q", tss, 4, KSTACK_TOP)           # RSP0
    struct.pack_into("<H", tss, 0x66, 0x68)              # no I/O bitmap
    b.write(KTSS, bytes(tss), user=False)

    pages, cpu = b.build(rip=USER_CODE, rsp=USER_STACK_TOP - 0x100)
    cpu.r10 = IOCTL_ALLOCATE_UAF
    cpu.rsi = USER_BUF_END
    cpu.rdx = 0
    cpu.r8 = FILE_HANDLE
    cpu.r9 = IOSB
    cpu.lstar = KERN_CODE
    cpu.star = (0x23 << 48) | (0x10 << 32)   # sysret 0x33/0x2B, syscall 0x10/0x18
    cpu.sfmask = 0x4700                      # TF IF DF NT, as Windows sets it
    cpu.gs_base = 0
    cpu.kernel_gs_base = KPCR
    cpu.idtr = GlobalSeg(base=KIDT, limit=0xFFF)
    cpu.gdtr = GlobalSeg(base=KGDT, limit=0x4F)
    cpu.tr = Seg(present=True, selector=0x40, base=KTSS, limit=0x67,
                 attr=0x8B)
    return pages, cpu


SYMBOLS = {
    "user!entry": USER_CODE,
    "user!finish": FINISH_GVA,
    "nt!KiSystemCall64": KERN_CODE,
    "nt!NtDeviceIoControlFile": K_IO_CONTROL,
    "nt!IofCallDriver": K_CALL_DRIVER,
    "hevd!IrpDeviceIoCtlHandler": K_DISPATCH,
    "nt!DbgPrint": K_DBGPRINT,
    "nt!KiPageFault": K_PF,
    "nt!KiGeneralProtectionFault": K_GP,
    "nt!KiDivideErrorFault": K_DE,
    "nt!KeBugCheck2": K_BUGCHECK,
}


def build_snapshot() -> Snapshot:
    b = SyntheticSnapshotBuilder()
    pool = [POOL + i * POOL_STRIDE for i in range(len(POOL_PAGES))]
    for gva, page in zip(pool, pool_pages(pool)):
        b.write(gva, page, user=False)
    pages, cpu = lay_out(b, pool)
    return Snapshot.from_pages(pages, cpu, symbols=dict(SYMBOLS))


def request(code: int, payload: bytes = b"") -> bytes:
    """A testcase: `u32 IoControlCode | payload` (fuzzer_hevd.cc:20-36)."""
    return struct.pack("<I", code) + payload


def seeds() -> List[bytes]:
    """One well-formed request per ioctl, 4 to 60 bytes: what a campaign
    starts from when inputs/ is empty.  What a request points at lies at
    the end of the user data page, as the payload lies at the end of its
    own, and a size says exactly what is there."""
    q = struct.Struct("<Q").pack
    d = struct.Struct("<I").pack
    end = USER_DATA + 0x1000
    return [
        request(IOCTL_STACK_OVERFLOW, d(52) + b"A" * 52),
        request(IOCTL_STACK_OVERFLOW_GS, d(52) + b"B" * 52),
        request(IOCTL_ARBITRARY_WRITE, q(end - 8) + q(end - 0x10)),
        request(IOCTL_POOL_OVERFLOW, d(52) + b"C" * 52),
        request(IOCTL_ALLOCATE_UAF),
        request(IOCTL_USE_UAF),
        request(IOCTL_FREE_UAF),
        request(IOCTL_ALLOCATE_FAKE, q(K_CALLBACK) + b"D" * 0x30),
        request(IOCTL_TYPE_CONFUSION, q(1) + q(USER_OBJECT)),
        request(IOCTL_INTEGER_OVERFLOW, d(52) + b"E" * 52),
        request(IOCTL_NULL_POINTER, d(MAGIC) + d(0)),
        request(IOCTL_UNINIT_STACK, d(MAGIC) + q(0x4141414141414141)),
        request(IOCTL_UNINIT_POOL, d(MAGIC) + d(0)),
        request(IOCTL_DOUBLE_FETCH, q(end - 0x20) + q(0x20)),
    ]


def _init(backend) -> bool:
    backend.set_breakpoint(FINISH_GVA, lambda b: b.stop(Ok()))
    # DbgPrint returns at once (fuzzer_hevd.cc:76-112 does the same to
    # nt!DbgPrintEx and friends)
    backend.set_breakpoint(
        K_DBGPRINT, lambda b: b.simulate_return_from_function(0))
    crash_detection.setup_kernel_crash_detection(backend)
    return True


def _insert_testcase(backend, data: bytes) -> bool:
    data = data.ljust(4, b"\x00")
    (code,) = struct.unpack_from("<I", data, 0)
    payload = data[4:4 + MAX_PAYLOAD]
    # the payload ENDS at the end of the page: one byte read past it
    # faults (fuzzer_hevd.cc:38-59)
    gva = USER_BUF_END - len(payload)
    if payload:
        backend.virt_write(gva, payload)
    backend.set_reg(10, code)           # r10 = IoControlCode
    backend.set_reg(6, gva)             # rsi = buffer
    backend.set_reg(2, len(payload))    # rdx = length
    return True


TARGET = Target(
    name="demo_hevd",
    init=_init,
    insert_testcase=_insert_testcase,
    snapshot=build_snapshot,
    seeds=seeds,
)
